"""Driver for coded training with one coded worker per chip.

As ``train`` (the window, the token source and the check steps are
defined as there), but the ``CodedTrainer`` is given the cell's chips, one
per worker, so that the decode-weighted combine is a cross-chip reduction,
and the model is DeepSeek-V2-Lite's layers: the weights are drawn in the
layout of ``chipbench.reference.dsv2``, replicated over the chips (the AdamW
moments split over them), and the
reference that decides ``correct`` is that file's, fed the rows of the
check steps and the straggler masks the program drew for them (it decodes
them itself).  The window also reports the expert layers' counters
(``moe_routed``, ``moe_dropped``, ``moe_peak``) over its steps.
"""
from __future__ import annotations

import dataclasses
import re

import numpy as np

from chipbench.reference import dsv2
from chipbench.reference import lm as ref
from chipbench.traffic import train

COUNTERS = ("moe_routed", "moe_dropped", "moe_peak")
# an HLO instruction whose opcode is a collective: name, opcode, -start or
# -done, first operand (the type before the opcode may hold spaces)
COLLECTIVE = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = .*?\s(all-reduce|reduce-scatter|all-gather|"
    r"all-to-all|collective-permute)(-start|-done)?\(%?([^,)\s]*)", re.M)


class Session(train.Session):
    def arch(self):
        from repro.configs import ARCHS, YarnScaling
        c, rs = self.cfg, self.cfg["rope_scaling"]
        return ARCHS[c["arch"]].with_overrides(
            n_layers=c["num_hidden_layers"],
            first_dense_layers=c["first_k_dense_replace"],
            vocab=c["vocab_size"], d_model=c["hidden_size"],
            n_heads=c["num_attention_heads"], n_kv=c["num_key_value_heads"],
            d_ff=c["intermediate_size"], moe_d_ff=c["moe_intermediate_size"],
            n_experts=c["router_experts"], experts_held=c["n_routed_experts"],
            expert_offset=c["expert_offset"], top_k=c["num_experts_per_tok"],
            n_shared_experts=c["n_shared_experts"],
            moe_renormalize=c["norm_topk_prob"],
            capacity_factor=c["capacity_factor"],
            router_aux_weight=c["aux_loss_alpha"],
            kv_lora_rank=c["kv_lora_rank"],
            qk_nope_head_dim=c["qk_nope_head_dim"],
            qk_rope_head_dim=c["qk_rope_head_dim"],
            v_head_dim=c["v_head_dim"], rope_theta=c["rope_theta"],
            rope_yarn=YarnScaling(
                factor=rs["factor"],
                original_max_position=rs["original_max_position_embeddings"],
                beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
                mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"]),
            attn_chunk=c["attn_chunk"], param_dtype=c["param_dtype"],
            dtype=c["compute_dtype"], optstate_dtype=c["optstate_dtype"])

    def layout(self) -> dict:
        return dsv2.layout(self.cfg)

    # -- set-up --------------------------------------------------------------
    def build(self) -> None:
        """``train``'s trainer and feed, the trainer made again on the
        cell's chips, one per worker."""
        from repro.train.coded import CodedTrainer
        super().build()
        one = self.trainer
        self.trainer = CodedTrainer(one.cfg, self.tcfg, one.engine,
                                    devices=self.devices)
        self.trainer.batcher = one.batcher
        self._note_step_args()

    def _note_step_args(self) -> None:
        """Keep the shapes, dtypes and shardings of the step's arguments at
        its first call, for ``step_collectives``."""
        import jax
        step = self.trainer._step

        def first(*args):
            self.step_args = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=a.sharding), args)
            self.trainer._step = step
            return step(*args)
        self.trainer._step = first

    def step_collectives(self) -> dict:
        """{instruction name: (opcode, first operand)} of the collectives
        in the compiled step, read from its HLO text; the step compiled at
        its first call, so this finds it in jit's cache."""
        text = self.trainer._step.lower(*self.step_args).compile().as_text()
        return {name: (op + (phase or ""), arg)
                for name, op, phase, arg in COLLECTIVE.findall(text)}

    def first_steps(self) -> None:
        """Weights from the seed, replicated over the chips, and a fresh
        AdamW state split over them (``CodedTrainer.place``), then the
        check's steps (one, then the rest) through the window's call; the
        masks the program drew for them are kept for the reference."""
        import jax
        import jax.numpy as jnp
        c, wl = self.cfg, self.wl
        n_check = int(wl["check"]["steps"])
        lay = self.layout()
        params, opt = self.trainer.place(
            ref.init(lay, self.seed, jnp.dtype(c["param_dtype"])))
        host0 = jax.device_get(params)
        losses, self.masks = [], []
        cb = lambda rec: losses.append(rec["loss"])
        for steps in (1, n_check - 1):
            self.trainer.tcfg = dataclasses.replace(self.tcfg, steps=steps)
            params, opt, _ = self.trainer.run(params, opt, cb)
            self.masks.extend(np.asarray(
                self.trainer.last_schedule.masks)[:steps])
            if steps == 1:
                self.prog_grad1 = {
                    k: v / (1.0 - c["optimizer"]["b1"])
                    for k, v in ref.leaf_norms(opt.m).items()}
        self.prog_change = ref.host_change_norms(jax.device_get(params),
                                                 host0)
        self.prog_loss = losses
        self.n_params = sum(int(np.prod(v[0])) for v in lay.values())
        self.collectives = self.step_collectives()
        self.state = (params, opt)
        self.trainer.tcfg = self.tcfg

    # -- window --------------------------------------------------------------
    def window(self, seconds: float):
        before = self.trainer.metrics.summary()
        record = super().window(seconds)
        after = self.trainer.metrics.summary()
        for k in COUNTERS:
            record.counts[k] = after.get(k, 0) - before.get(k, 0)
        return record

    # -- correctness ---------------------------------------------------------
    def reference(self, precision: str = "highest") -> dict:
        """The reference's check steps from the same weights, rows and
        straggler masks."""
        import jax.numpy as jnp
        params0 = ref.init(self.layout(), self.seed,
                           jnp.dtype(self.cfg["param_dtype"]))
        return dsv2.train(params0, list(zip(self.tokens.kept, self.masks)),
                          cfg=self.cfg, total_steps=self.wl["max_steps"],
                          precision=precision)

    def calibrate(self) -> dict:
        """The program's readings, the control's (the reference in int8 in
        the program's place) and a half-batch fault's (the program with the
        second half of the data groups left out of the combine
        coefficients and the first doubled)."""
        from chipbench.calibrate import _HalfBatch
        self.release()
        want = self.reference()
        ctl = self.reference("int8")
        half = Session(self.cfg, self.wl, self.seed, self.devices)
        half.build()
        half.trainer.batcher = _HalfBatch(half.trainer.batcher,
                                          half.trainer.code)
        half.first_steps()
        half.release()
        return {"program": self.compare(self.prog_loss, self.prog_grad1,
                                        self.prog_change, want),
                "control": self.compare(ctl["loss"], ctl["grad1"],
                                        ctl["change"], want),
                "fault_half": self.compare(half.prog_loss, half.prog_grad1,
                                           half.prog_change, want)}
