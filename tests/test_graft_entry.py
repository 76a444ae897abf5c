"""Driver entry points: ``dryrun_multichip`` runs on the devices the
process has, or raises — it never provisions a mesh the caller did not
ask for (a run that fell back to virtual CPU devices would report
collectives that never crossed a wire)."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as graft


def test_dryrun_raises_with_too_few_devices():
    import jax
    have = len(jax.devices())
    with pytest.raises(RuntimeError, match=f"has {have} cpu device"):
        graft.dryrun_multichip(have + 1)


def test_dryrun_starts_no_process_and_no_thread(monkeypatch):
    """The old path answered a short device list by re-executing itself
    on a virtual mesh from a probe thread; neither may come back."""
    import subprocess
    import threading

    def forbidden(*a, **k):
        raise AssertionError("dryrun_multichip must not spawn")

    monkeypatch.setattr(subprocess, "run", forbidden)
    monkeypatch.setattr(subprocess, "Popen", forbidden)
    monkeypatch.setattr(threading.Thread, "start", forbidden)
    with pytest.raises(RuntimeError, match="device"):
        graft.dryrun_multichip(10 ** 6)
    assert not os.environ.get("MXNET_DRYRUN_CHILD")


@pytest.mark.slow
def test_dryrun_runs_on_the_mesh_the_caller_provided(capsys):
    """tests/conftest.py asked for 8 virtual CPU devices: the data+tensor
    parallel step compiles and runs on exactly those."""
    graft.dryrun_multichip(2)
    out = capsys.readouterr().out
    assert "dryrun_multichip ok: mesh=(1x2)" in out
    assert "dryrun zero-sharded ok: dp=2" in out
