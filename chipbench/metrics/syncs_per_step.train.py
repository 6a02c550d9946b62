"""Points per coded training step at which the host waits on the device:
the counters ``host_syncs`` over ``steps`` of the trainer's
``MetricsRegistry`` (``train.coded.CodedTrainer.metrics``), over every step
the trainer ran."""


def read(ctx):
    trainer = getattr(ctx.session, "trainer", None)
    registry = getattr(trainer, "metrics", None)
    if registry is None:
        return None
    counts = registry.summary()
    if not counts.get("steps"):
        return None
    return counts.get("host_syncs", 0) / counts["steps"]
