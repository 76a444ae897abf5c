"""Ops-facing tools: parse_log, rec2idx, bandwidth/measure, diagnose,
and launch.py's stop protocol and one-process-per-chip rule.

Reference analogs: tools/parse_log.py, tools/rec2idx.py,
tools/bandwidth/measure.py, tools/diagnose.py.
"""
import importlib.util
import os
import signal
import subprocess
import sys
import time

import numpy as onp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(relpath, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# parse_log
# ---------------------------------------------------------------------------

def test_parse_log_reference_grammar(tmp_path, capsys):
    log = tmp_path / "train.log"
    log.write_text(
        "INFO Epoch[0] Train-accuracy=0.70\n"
        "INFO Epoch[0] Validation-accuracy=0.65\n"
        "INFO Epoch[0] Time cost=12.5\n"
        "INFO Epoch[1] Train-accuracy=0.80\n"
        "INFO Epoch[1] Validation-accuracy=0.75\n"
        "INFO Epoch[1] Time cost=11.0\n")
    parse_log = _load("tools/parse_log.py", "parse_log")
    parse_log.main([str(log)])
    out = capsys.readouterr().out
    assert "| epoch |" in out and "train-accuracy" in out
    assert "0.700000" in out and "0.750000" in out and "11.0" in out

    parse_log.main([str(log), "--format", "none"])
    out = capsys.readouterr().out
    assert out.startswith("epoch\t")


def test_parse_log_estimator_grammar(tmp_path, capsys):
    log = tmp_path / "est.log"
    log.write_text("[Epoch 0] train accuracy: 0.5\n"
                   "[Epoch 0] validation accuracy: 0.4\n"
                   "[Epoch 0] time used: 3.2\n")
    parse_log = _load("tools/parse_log.py", "parse_log2")
    parse_log.main([str(log)])
    out = capsys.readouterr().out
    assert "0.500000" in out and "0.400000" in out


# ---------------------------------------------------------------------------
# rec2idx
# ---------------------------------------------------------------------------

def test_rec2idx_roundtrip(tmp_path, capsys):
    from mxnet_tpu import recordio
    rec_path = str(tmp_path / "data.rec")
    idx_path = str(tmp_path / "data.idx")
    payloads = [bytes([i]) * (10 + i) for i in range(5)]
    w = recordio.MXRecordIO(rec_path, "w")
    for p in payloads:
        w.write(p)
    w.close()

    rec2idx = _load("tools/rec2idx.py", "rec2idx")
    assert rec2idx.main([rec_path, idx_path]) == 0
    assert "indexed 5 records" in capsys.readouterr().out

    r = recordio.MXIndexedRecordIO(idx_path, rec_path, "r")
    assert len(r.keys) == 5
    for i, p in enumerate(payloads):
        assert r.read_idx(i) == p
    assert r.read_idx(3) == payloads[3]  # random access after seek
    r.close()


# ---------------------------------------------------------------------------
# bandwidth / diagnose
# ---------------------------------------------------------------------------

def test_bandwidth_measure_local():
    measure = _load("tools/bandwidth/measure.py", "bw_measure")
    args = measure.parse_args(["--network", "resnet18_v1",
                               "--kv-store", "local",
                               "--num-batches", "2",
                               "--num-classes", "10"])
    result = measure.run(args)
    assert result["gbps"] > 0
    assert result["params_mb"] > 10  # resnet18 is ~45 MB of params


def test_bandwidth_measure_detects_corruption(monkeypatch):
    measure = _load("tools/bandwidth/measure.py", "bw_measure2")
    assert measure.error([], []) == 0


def test_diagnose_smoke(capsys):
    diagnose = _load("tools/diagnose.py", "diagnose")
    assert diagnose.main([]) == 0
    out = capsys.readouterr().out
    for section in ("Python Info", "MXNet(TPU) Info", "Accelerator Info",
                    "Environment"):
        assert section in out
    assert "Network Test" not in out  # egress checks are opt-in
    assert "Program Analysis" not in out  # analysis section is opt-in


def test_diagnose_analysis_section(capsys):
    """--analysis: env reports include compiled-program health — the
    tiny-MLP fused step's ProgramReport with an OK verdict."""
    diagnose = _load("tools/diagnose.py", "diagnose2")
    assert diagnose.main(["--analysis"]) == 0
    out = capsys.readouterr().out
    assert "Program Analysis" in out
    assert "ProgramReport(mode=fused" in out
    assert "verdict      : OK" in out


def test_diagnose_fusion_section(capsys):
    """--fusion: the census prints a kernel table for both canonical
    legs (tiny MLP + the LSTM-LM example architecture) with bound
    classes and the stranded-op verdict."""
    diagnose = _load("tools/diagnose.py", "diagnose4")
    assert diagnose.main(["--fusion"]) == 0
    out = capsys.readouterr().out
    assert "Fusion Census" in out
    assert "tiny MLP" in out and "LSTM LM" in out
    assert "fusions=" in out and "boundary_bytes=" in out
    assert "memory" in out            # bound class column populated
    assert "stranded ops : none above the" in out


def test_diagnose_sharding_section(capsys):
    """--sharding: the zero-sharded MLP's sharding-flow table (buffers
    with resolved layouts), the implicit-reshard verdict, and the
    per-axis communication cost table."""
    diagnose = _load("tools/diagnose.py", "diagnose_sh")
    assert diagnose.main(["--sharding"]) == 0
    out = capsys.readouterr().out
    assert "Sharding Analysis" in out
    assert "pack=zero-dp" in out
    assert "P(dp)" in out                       # resolved state shard
    assert "implicit reshards: none above the" in out
    assert "axis 'dp':" in out                  # per-axis cost line
    assert "table digest:" in out


def test_diagnose_kernels_section(capsys):
    """--kernels: the per-kernel dispatch table (path + reason for
    every kernel the gate knows) and the interpret-vs-xla parity
    probes, bit-exact on this backend."""
    diagnose = _load("tools/diagnose.py", "diagnose5")
    assert diagnose.main(["--kernels"]) == 0
    out = capsys.readouterr().out
    assert "Pallas Kernel Layer" in out
    assert "MXNET_PALLAS=" in out
    for name in ("rnn_scan", "opt_update", "layernorm", "bias_gelu",
                 "flash_attention"):
        assert name in out
    assert out.count("bit-exact") == 2


def test_diagnose_numerics_section(capsys, tmp_path, monkeypatch):
    """--numerics: the 10-step norm table prints with finite values and
    the simulated-divergence demo produces exactly one anomaly plus a
    post-mortem dump in MXNET_NUMERICS_DUMP_DIR."""
    from mxnet_tpu import telemetry
    telemetry.reset()
    monkeypatch.setenv("MXNET_NUMERICS_DUMP_DIR", str(tmp_path))
    diagnose = _load("tools/diagnose.py", "diagnose3")
    assert diagnose.main(["--numerics"]) == 0
    out = capsys.readouterr().out
    assert "Training Numerics" in out
    assert "grad_norm" in out and "upd/w ratio" in out
    assert "anomalies    : 1 (want exactly 1)" in out
    assert list(tmp_path.glob("mx_numerics_*.json"))
    telemetry.reset()


def test_diagnose_serving_section(capsys):
    """--serving: AOT-compiles the tiny bucketed predictor, runs a
    concurrent closed-loop burst through the dynamic batcher, and
    prints the stats table plus the p50/p99 latency probe — then the
    resilience panel: one injected revocation under a burst with
    breaker transitions, recovery downtime, and the outcome census."""
    diagnose = _load("tools/diagnose.py", "diagnose7")
    assert diagnose.main(["--serving"]) == 0
    out = capsys.readouterr().out
    assert "Inference Serving" in out
    assert "4 programs" in out            # one per shape bucket
    assert "throughput   :" in out and "req/s" in out
    assert "latency      : p50" in out and "p99" in out
    assert "batch fill" in out
    assert "errors        0" in out
    assert "compile cache:" in out
    # resilience panel: exactly one recovery, breaker round trip
    assert "resilience (1 injected revocation under burst)" in out
    assert "recoveries   : 1" in out
    assert "closed -> open -> half_open -> closed" in out
    assert "outcomes     :" in out
    assert "shed policy  : MXNET_SERVING_SHED=" in out


def test_diagnose_decode_section(capsys):
    """--decode: AOT-compiles the continuous-batching decode engine
    over its slot ladder, runs a 6-request streamed burst, and prints
    the mid-burst slot table, the page-allocator census, the TTFT/TPOT
    probe and the decode-kernel dispatch decision."""
    diagnose = _load("tools/diagnose.py", "diagnose_dec")
    assert diagnose.main(["--decode"]) == 0
    out = capsys.readouterr().out
    assert "Continuous-Batching Decode" in out
    assert "slot ladder" in out and "prefill chunk" in out
    assert "-- slot table (mid-burst) --" in out
    assert "-- page allocator --" in out
    assert "used_pages" in out and "bytes_per_page" in out
    assert "-- streamed burst --" in out
    assert "ttft" in out and "tpot" in out and "tok/s" in out
    assert "decode kernel:" in out and "MXNET_PALLAS=" in out
    # speculative panel: drafter line, acceptance histogram, shared/COW
    # page census
    assert "-- speculative decode --" in out
    assert "MXNET_DECODE_SPEC_K" in out
    assert "drafter      : NgramDrafter" in out
    assert "verify steps :" in out and "accept" in out
    assert "prefix cache :" in out and "COW copies" in out
    assert "decode check failed" not in out


def test_diagnose_elastic_section(capsys):
    """--elastic: runs a tiny supervised TrainLoop, injects one mid-run
    fault, and prints the RecoveryLog table (exactly one recovery) and
    the restore provenance."""
    from mxnet_tpu.testing import faults
    diagnose = _load("tools/diagnose.py", "diagnose6")
    try:
        assert diagnose.main(["--elastic"]) == 0
    finally:
        faults.reset()
    out = capsys.readouterr().out
    assert "Elastic Supervisor" in out
    assert "1 recovery(ies)" in out
    assert "provenance   : restored step" in out
    assert "-- recovery log --" in out
    assert ("device_lost" in out) or ("transient" in out)


def test_diagnose_threads_section(capsys):
    """--threads: prints the audited-lock table, the observed
    lock-order graph's cycle status, a planted two-lock inversion demo
    (on a private graph — the global hierarchy stays clean), and a
    contention snapshot with a live waiter."""
    from mxnet_tpu.analysis import threads
    diagnose = _load("tools/diagnose.py", "diagnose_thr")
    assert diagnose.main(["--threads"]) == 0
    out = capsys.readouterr().out
    assert "Concurrency Audit" in out
    assert "MXNET_LOCK_STALL_SEC=" in out
    assert "-- audited locks" in out
    assert "order graph" in out
    assert "-- planted inversion demo (1 finding) --" in out
    assert "demo.inversion.a" in out and "demo.inversion.b" in out
    assert "-- contention snapshot --" in out
    assert "demo.contention" in out and "1 waiter(s)" in out
    # the demo's inversion must NOT have leaked into the global graph
    assert not any("demo.inversion" in f"{a}{b}"
                   for a, b in threads.graph().edge_pairs())


def test_diagnose_overlap_section(capsys):
    """--overlap: compiles the zero-sharded adam MLP serial AND
    bucketed on the virtual dp mesh and prints each schedule's
    exposed-communication table (docs/PERF_NOTES.md \"Communication
    overlap\")."""
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("overlap section needs a >=2-device mesh")
    diagnose = _load("tools/diagnose.py", "diagnose7")
    assert diagnose.main(["--overlap"]) == 0
    out = capsys.readouterr().out
    assert "Communication Overlap" in out
    assert "serial (bucket_bytes=0)" in out
    assert "bucketed (bucket_bytes=16384)" in out
    assert "exposed=" in out and "collective" in out
    assert "overlap check failed" not in out


# ---------------------------------------------------------------------------
# launch.py graceful stop
# ---------------------------------------------------------------------------

def _spawn(code):
    """Start a child and block until its signal handlers are installed
    (it prints 'ready')."""
    p = subprocess.Popen([sys.executable, "-c", code],
                         stdout=subprocess.PIPE)
    assert p.stdout.readline().strip() == b"ready"
    return p


_READY = "import sys; print('ready'); sys.stdout.flush()\n"


def test_graceful_stop_grace_then_kill():
    launch = _load("tools/launch.py", "launch_mod")
    # p1 exits promptly on SIGTERM; p2 ignores SIGTERM and is hard-killed
    # after the grace window: no worker outlives the launcher
    p1 = _spawn("import signal,time\n"
                "signal.signal(signal.SIGTERM, lambda *a: exit(0))\n"
                + _READY + "time.sleep(60)")
    p2 = _spawn("import signal,time\n"
                "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
                + _READY + "time.sleep(60)")
    t0 = time.time()
    launch._graceful_stop([p1, p2], grace=1.0)
    p1.wait(timeout=5)
    p2.wait(timeout=5)
    assert time.time() - t0 < 10
    assert p1.returncode == 0          # exited via its SIGTERM handler
    assert p2.returncode == -signal.SIGKILL  # escalated


def test_may_own_accelerator():
    launch = _load("tools/launch.py", "launch_mod3")
    assert launch._may_own_accelerator({}) is True
    assert launch._may_own_accelerator({"JAX_PLATFORMS": "cpu"}) is False
    assert launch._may_own_accelerator({"JAX_PLATFORMS": "tpu"}) is True


def test_launch_local_refuses_two_accelerator_owners(monkeypatch):
    """Every local child inherits the same environment, so two that may
    claim the accelerator would reach for the same chips: refused before
    anything is spawned. One such worker, or CPU-pinned ones, start."""
    launch = _load("tools/launch.py", "launch_mod4")
    spawned = []
    monkeypatch.setattr(launch.subprocess, "Popen",
                        lambda cmd, env: spawned.append(env) or _Done())
    monkeypatch.setattr(launch.signal, "signal", lambda *a: None)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit, match="one process"):
        launch.launch_local(2, ["true"], 9091)
    assert spawned == []
    assert launch.launch_local(1, ["true"], 9091) == 0
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert launch.launch_local(2, ["true"], 9091) == 0
    assert [e["DMLC_WORKER_ID"] for e in spawned] == ["0", "0", "1"]


class _Done:
    """A finished child."""
    pid = 0

    def poll(self):
        return 0
