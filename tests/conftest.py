import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _isolated_runstore(tmp_path, monkeypatch):
    """Point the run store at a per-test tmp dir so executing experiments
    in tests never writes manifests into the repo's runs/store."""
    monkeypatch.setenv("REPRO_RUNSTORE", str(tmp_path / "runstore"))
