"""Traffic drivers, one per kind of entry point; a workload file names its
driver by module name (``"driver": "solve"``)."""
