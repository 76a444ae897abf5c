"""Communication overlap & latency hiding (PR 17).

Covers the exposed-communication analysis pass (analysis/overlap.py)
on canned schedules — sync dependency-slack windows, async start/done
spans, movement transparency, root-escape deadlines, taint exclusion —
the baseline regression gate (unit bands + the tier-1 ``lint``-marked
sweep against tests/fixtures/overlap_baselines.json), and the bucketed
ZeRO gradient path it measures: reverse-topological bucket schedules,
the bucketed reduce-scatter/all-gather routing with non-divisible
tails, bit-exact loss/param parity of bucketed vs monolithic updates,
the per-payload-byte comm-cost invariant (N buckets of B bytes cost
one collective of N*B bytes), the double-buffered pipeline permute, and
the transfer-guard-armed pipelined run.

Acceptance bar of ISSUE 17: the bucketed zero program on the virtual
dp=8 mesh measures overlap_fraction > 0 where the serial monolithic
baseline measures ~0 (zero at metric resolution: the only residual
hider is the nanoseconds-scale loss tail the scheduler may park after
the weight all-gather).
"""
import contextlib
import json
import math
import os
import textwrap

import numpy as onp
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

import mxnet_tpu as mx
from mxnet_tpu import nd, telemetry
from mxnet_tpu.analysis import guard as tguard
from mxnet_tpu.analysis import overlap as aoverlap
from mxnet_tpu.analysis import sharding as asharding
from mxnet_tpu.analysis.report import CollectiveOp, CollectiveStats
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import Trainer, TrainLoop, nn
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.gluon.fused_step import zero_bucket_schedule
from mxnet_tpu.parallel import make_mesh, shard_batch
from mxnet_tpu.parallel.collectives import (allgather_bucketed,
                                            reduce_scatter_bucketed)
from mxnet_tpu.telemetry import names as tn

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
BASELINES = os.path.join(FIXTURES, "overlap_baselines.json")

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device virtual mesh")

DP = 4


@contextlib.contextmanager
def zero_knobs(bucket_bytes=None, min_size=None):
    """The two ZeRO packing knobs, set in the environment for the body."""
    with pytest.MonkeyPatch.context() as mp:
        if bucket_bytes is not None:
            mp.setenv("MXNET_ZERO_BUCKET_BYTES", str(bucket_bytes))
        if min_size is not None:
            mp.setenv("MXNET_ZERO_SHARD_MIN_SIZE", str(min_size))
        yield


# ---------------------------------------------------------------------------
# canned-schedule censuses: window grammar, hider accounting
# ---------------------------------------------------------------------------

# a collective whose value reaches the ROOT tuple through plumbing
# only (bitcast): its deadline is program completion, so the trailing
# independent dot hides it.  Hiders must be flops-bearing kernels —
# the fusion census prices standalone dots, not standalone plumbing.
_CANNED_ROOT_ESCAPE = textwrap.dedent("""\
HloModule jit_step, is_scheduled=true, entry_computation_layout={(f32[128,128]{1,0}, f32[128,128]{1,0})->(f32[16,128]{1,0}, f32[128,128]{1,0})}

ENTRY %main (p0: f32[128,128], p1: f32[128,128]) -> (f32[16,128], f32[128,128]) {
  %p0 = f32[128,128]{1,0} parameter(0)
  %p1 = f32[128,128]{1,0} parameter(1)
  %reduce-scatter.1 = f32[16,128]{1,0} reduce-scatter(f32[128,128]{1,0} %p0), channel_id=1, replica_groups=[1,8]<=[8], use_global_device_ids=true, dimensions={0}, to_apply=%add
  %dot.1 = f32[128,128]{1,0} dot(f32[128,128]{1,0} %p1, f32[128,128]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %bitcast.1 = f32[16,128]{1,0} bitcast(f32[16,128]{1,0} %reduce-scatter.1)
  ROOT %tuple.1 = (f32[16,128]{1,0}, f32[128,128]{1,0}) tuple(f32[16,128]{1,0} %bitcast.1, f32[128,128]{1,0} %dot.1)
}
""")

# the dot CONSUMES the reduce-scatter: the window closes at the
# consumer and the tainted dot cannot hide its own producer
_CANNED_DEPENDENT = textwrap.dedent("""\
HloModule jit_step, is_scheduled=true, entry_computation_layout={(f32[128,128]{1,0}, f32[128,128]{1,0})->f32[16,128]{1,0}}

ENTRY %main (p0: f32[128,128], p1: f32[128,128]) -> f32[16,128] {
  %p0 = f32[128,128]{1,0} parameter(0)
  %p1 = f32[128,128]{1,0} parameter(1)
  %reduce-scatter.1 = f32[16,128]{1,0} reduce-scatter(f32[128,128]{1,0} %p0), channel_id=1, replica_groups=[1,8]<=[8], use_global_device_ids=true, dimensions={0}, to_apply=%add
  ROOT %dot.1 = f32[16,128]{1,0} dot(f32[16,128]{1,0} %reduce-scatter.1, f32[128,128]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
""")

# async start/done pair: the window is the scheduler's explicit span,
# and the dot placed inside it hides the wire time
_CANNED_ASYNC = textwrap.dedent("""\
HloModule jit_step, is_scheduled=true, entry_computation_layout={(f32[256]{0}, f32[128,128]{1,0})->(f32[256]{0}, f32[128,128]{1,0})}

ENTRY %main (p0: f32[256], p1: f32[128,128]) -> (f32[256], f32[128,128]) {
  %p0 = f32[256]{0} parameter(0)
  %p1 = f32[128,128]{1,0} parameter(1)
  %all-reduce-start.1 = f32[256]{0} all-reduce-start(f32[256]{0} %p0), channel_id=1, replica_groups=[1,8]<=[8], use_global_device_ids=true, to_apply=%add
  %dot.1 = f32[128,128]{1,0} dot(f32[128,128]{1,0} %p1, f32[128,128]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %all-reduce-done.1 = f32[256]{0} all-reduce-done(f32[256]{0} %all-reduce-start.1)
  ROOT %tuple.1 = (f32[256]{0}, f32[128,128]{1,0}) tuple(f32[256]{0} %all-reduce-done.1, f32[128,128]{1,0} %dot.1)
}
""")

# a movement-only fusion (slice writeback) consuming the collective is
# followed TRANSPARENTLY: it neither closes the window nor counts as a
# hider, so the trailing independent dot still hides the wire time
_CANNED_MOVEMENT = textwrap.dedent("""\
HloModule jit_step, is_scheduled=true, entry_computation_layout={(f32[128,128]{1,0}, f32[128,128]{1,0})->(f32[8,128]{1,0}, f32[128,128]{1,0})}

%fused_movement (param_0.1: f32[16,128]) -> f32[8,128] {
  %param_0.1 = f32[16,128]{1,0} parameter(0)
  ROOT %slice.1 = f32[8,128]{1,0} slice(f32[16,128]{1,0} %param_0.1), slice={[0:8], [0:128]}
}

ENTRY %main (p0: f32[128,128], p1: f32[128,128]) -> (f32[8,128], f32[128,128]) {
  %p0 = f32[128,128]{1,0} parameter(0)
  %p1 = f32[128,128]{1,0} parameter(1)
  %reduce-scatter.1 = f32[16,128]{1,0} reduce-scatter(f32[128,128]{1,0} %p0), channel_id=1, replica_groups=[1,8]<=[8], use_global_device_ids=true, dimensions={0}, to_apply=%add
  %fusion.1 = f32[8,128]{1,0} fusion(f32[16,128]{1,0} %reduce-scatter.1), kind=kLoop, calls=%fused_movement
  %dot.1 = f32[128,128]{1,0} dot(f32[128,128]{1,0} %p1, f32[128,128]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %tuple.1 = (f32[8,128]{1,0}, f32[128,128]{1,0}) tuple(f32[8,128]{1,0} %fusion.1, f32[128,128]{1,0} %dot.1)
}
""")


def test_root_escape_window_extends_to_schedule_end():
    rep = aoverlap.overlap_census(_CANNED_ROOT_ESCAPE, num_devices=8)
    assert rep.scheduled and rep.n_collectives == 1
    [w] = rep.windows
    assert w.kind == "reduce_scatter" and not w.is_async
    # value escapes through bitcast into the root tuple: deadline is
    # program completion (end == schedule length, 6 entry ops)
    assert w.window == (0, 6)
    assert w.n_hiders == 1                  # the independent dot
    assert w.comm_s > 0 and w.hide_s > 0
    assert w.exposed_s == pytest.approx(max(0.0, w.comm_s - w.hide_s))
    assert rep.overlap_fraction > 0.0


def test_dependent_consumer_closes_window_and_cannot_hide():
    rep = aoverlap.overlap_census(_CANNED_DEPENDENT, num_devices=8)
    [w] = rep.windows
    # the dot NEEDS the bytes: window closes there, and the tainted
    # consumer is never credited as a hider
    assert w.window[1] == 3 and w.n_hiders == 0
    assert w.hide_s == 0.0
    assert w.exposed_s == pytest.approx(w.comm_s)
    assert rep.overlap_fraction == pytest.approx(0.0)


def test_async_pair_window_is_start_done_span():
    rep = aoverlap.overlap_census(_CANNED_ASYNC, num_devices=8)
    assert rep.n_collectives == 1 and rep.n_async == 1
    [w] = rep.windows
    assert w.is_async
    # schedule: p0 p1 start dot done tuple -> span (2, 4)
    assert w.window == (2, 4)
    assert w.n_hiders == 1 and w.hide_s > 0


def test_movement_fusion_is_transparent_and_unpriced():
    secs, movement = aoverlap._kernel_tables(_CANNED_MOVEMENT)
    assert "fusion.1" in movement and "fusion.1" not in secs
    assert "dot.1" in secs
    rep = aoverlap.overlap_census(_CANNED_MOVEMENT, num_devices=8)
    [w] = rep.windows
    # slice writeback carries no deadline: window runs to the end and
    # the dot AFTER the movement fusion still hides the collective
    assert w.window == (0, 6)
    assert w.n_hiders == 1 and w.hide_s > 0


def test_report_brief_and_table():
    rep = aoverlap.overlap_census(_CANNED_ROOT_ESCAPE, num_devices=8)
    b = rep.brief()
    for k in ("exposed_comm_s", "total_comm_s", "overlap_fraction",
              "n_collectives", "n_async"):
        assert k in b
    d = rep.to_dict()
    assert d["scheduled"] is True and d["windows"]
    assert "exposed=" in rep.summary_line()
    assert "reduce-scatter.1" in rep.table_str()


def test_unparseable_hlo_degrades_to_empty_report():
    rep = aoverlap.overlap_census("not hlo at all", num_devices=8)
    assert rep.n_collectives == 0 and rep.total_comm_s == 0.0
    assert rep.overlap_fraction == 0.0


# ---------------------------------------------------------------------------
# bucket schedule (gluon/fused_step.py)
# ---------------------------------------------------------------------------

def _unit(padded, upd="float32", fwd="float32"):
    return {"padded": padded, "upd_dtype": upd, "dtypes": [fwd]}


def test_bucket_schedule_serial_is_single_bucket_in_order():
    units = [_unit(256), _unit(256), _unit(256)]     # 1 KiB each
    assert zero_bucket_schedule(units, 0) == [[0, 1, 2]]
    assert zero_bucket_schedule(units, None) == [[0, 1, 2]]
    assert zero_bucket_schedule(units, -1) == [[0, 1, 2]]


def test_bucket_schedule_reverse_topological_and_size_bounded():
    units = [_unit(256), _unit(256), _unit(256)]
    # backward produces the LAST unit's gradient first
    assert zero_bucket_schedule(units, 1024) == [[2], [1], [0]]
    assert zero_bucket_schedule(units, 2048) == [[2, 1], [0]]
    assert zero_bucket_schedule(units, 1 << 30) == [[2, 1, 0]]
    # bucket smaller than every unit: units still ship, one per bucket
    assert zero_bucket_schedule(units, 1) == [[2], [1], [0]]


def test_bucket_schedule_never_mixes_dtypes():
    units = [_unit(256), _unit(256, upd="float16"), _unit(256)]
    for bb in (0, 1 << 30):
        sched = zero_bucket_schedule(units, bb)
        covered = sorted(k for b in sched for k in b)
        assert covered == [0, 1, 2]
        for b in sched:
            assert len({str(units[k]["upd_dtype"]) for k in b}) == 1


# ---------------------------------------------------------------------------
# bucketed collective routing (parallel/collectives.py)
# ---------------------------------------------------------------------------

def _segs(lens, seed=0):
    rng = onp.random.RandomState(seed)
    return [jnp.asarray(rng.randn(n).astype("float32")) for n in lens]


def test_reduce_scatter_bucketed_non_divisible_tails():
    segs = _segs((5, 7, 4))
    calls = []

    def constrain(buf):
        calls.append(tuple(buf.shape))
        return buf

    outs = reduce_scatter_bucketed(segs, 4, constrain=constrain)
    # ONE (num_shards, S) buffer: ceil(5/4) + ceil(7/4) + ceil(4/4)
    assert calls == [(4, 2 + 2 + 1)]
    for seg, out in zip(segs, outs):
        n = seg.shape[0]
        pad = (-n) % 4
        onp.testing.assert_array_equal(
            onp.asarray(out),
            onp.pad(onp.asarray(seg), (0, pad)))


def test_allgather_bucketed_round_trips_with_orig_lens():
    lens = (5, 7, 4)
    segs = _segs(lens, seed=1)
    shards = reduce_scatter_bucketed(segs, 4)
    back = allgather_bucketed(shards, 4, orig_lens=lens)
    for seg, full in zip(segs, back):
        onp.testing.assert_array_equal(onp.asarray(full),
                                       onp.asarray(seg))
    # without orig_lens the scatter padding stays on
    padded = allgather_bucketed(shards, 4)
    assert [int(p.shape[0]) for p in padded] == [8, 8, 4]


def test_allgather_bucketed_rejects_non_divisible_segment():
    with pytest.raises(MXNetError, match="not divisible"):
        allgather_bucketed([jnp.arange(5.0)], 4)


# ---------------------------------------------------------------------------
# per-payload-byte comm cost: bucketing leaves the modeled budget alone
# ---------------------------------------------------------------------------

def test_comm_cost_invariant_under_bucketing():
    """N bucketed collectives of B bytes each must cost what ONE
    collective of N*B bytes costs — otherwise the cost model would
    punish the overlap-motivated bucket split."""
    profile = asharding.bandwidth_profile()

    def _op(kind, elements, name, decomposed=False):
        return CollectiveOp(kind=kind, name=name, elements=elements,
                            dtype="f32", axes=("dp",), group_size=8,
                            decomposed=decomposed)

    for kind in ("all_gather", "reduce_scatter", "all_reduce"):
        many = asharding.comm_cost(CollectiveStats(ops=[
            _op(kind, 1024, f"{kind}.{i}") for i in range(8)]), profile)
        one = asharding.comm_cost(CollectiveStats(ops=[
            _op(kind, 8 * 1024, kind)]), profile)
        assert many.total_s > 0
        assert math.isclose(many.total_s, one.total_s, rel_tol=1e-9), \
            (kind, many.total_s, one.total_s)


# ---------------------------------------------------------------------------
# baseline regression gate
# ---------------------------------------------------------------------------

def _rep(exposed, total):
    r = aoverlap.OverlapReport()
    r.exposed_comm_s = float(exposed)
    r.total_comm_s = float(total)
    return r


def test_check_baseline_one_sided_bands():
    base = {"leg": {"exposed_comm_s": 1e-5, "overlap_fraction": 0.5,
                    "tol_pct": 25}}
    # within both bands
    assert aoverlap.check_baseline(_rep(1.1e-5, 2e-5), base, "leg") == []
    # improvement is never a finding
    assert aoverlap.check_baseline(_rep(1e-7, 2e-5), base, "leg") == []
    # exposure regressed AND fraction collapsed: both bands fire
    worse = aoverlap.check_baseline(_rep(2e-5, 2.01e-5), base, "leg")
    assert len(worse) == 2
    assert all(f.rule == "overlap-regression" and f.checker == "overlap"
               for f in worse)


def test_check_baseline_absolute_floors():
    base = {"leg": {"exposed_comm_s": 0.0, "overlap_fraction": 0.02,
                    "tol_pct": 10}}
    # 1 us absolute band on exposed seconds near zero
    assert aoverlap.check_baseline(_rep(5e-7, 1e-4), base, "leg") == []
    bad = aoverlap.check_baseline(_rep(2e-6, 1e-4), base, "leg")
    assert len(bad) == 1 and "exposed comm" in bad[0].message
    # 0.05 absolute fraction floor: a 0.02 baseline fraction cannot
    # fire the fraction band even when the measured fraction is 0
    frac_only = [f for f in aoverlap.check_baseline(
        _rep(1e-7, 1e-7), base, "leg") if "fraction" in f.message]
    assert frac_only == []


def test_check_baseline_missing_leg_warns():
    out = aoverlap.check_baseline(_rep(0, 0), {}, "nope")
    assert len(out) == 1
    assert out[0].severity == "warn"
    assert "no overlap baseline" in out[0].message


def test_baseline_from_env_parses_path_and_leg(monkeypatch, tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"_comment": "x",
                             "legA": {"exposed_comm_s": 1e-6}}))
    monkeypatch.setenv("MXNET_OVERLAP_BASELINE", str(p))
    bl, leg = aoverlap.baseline_from_env()
    assert leg is None and set(bl) == {"legA"}
    monkeypatch.setenv("MXNET_OVERLAP_BASELINE", f"{p}:legA")
    bl, leg = aoverlap.baseline_from_env()
    assert leg == "legA" and "legA" in bl
    monkeypatch.delenv("MXNET_OVERLAP_BASELINE")
    assert aoverlap.baseline_from_env() is None
    monkeypatch.setenv("MXNET_OVERLAP_BASELINE",
                       str(tmp_path / "missing.json"))
    assert aoverlap.baseline_from_env() is None


def test_checked_in_fixture_has_both_legs():
    bl = aoverlap.load_baselines(BASELINES)
    assert set(bl) == {"zero-serial", "zero-bucketed"}
    for leg in bl.values():
        assert leg["exposed_comm_s"] > 0 and "tol_pct" in leg


# ---------------------------------------------------------------------------
# the acceptance programs: serial vs bucketed zero step on dp=8
# ---------------------------------------------------------------------------

def _acceptance_census(bucket_bytes):
    """The canonical overlap-analysis program of tools/diagnose.py
    --overlap and docs/PERF_NOTES.md \"Communication overlap\"."""
    onp.random.seed(3)
    mx.random.seed(3)
    net = nn.HybridSequential()
    net.add(nn.Dense(64, in_units=32, activation="relu"),
            nn.Dense(48, activation="relu"), nn.Dense(10))
    net.initialize()
    loss = gloss.SoftmaxCrossEntropyLoss()
    x = nd.array(onp.random.randn(64, 32).astype("float32"))
    y = nd.array(onp.random.randint(0, 10, size=(64,))
                 .astype("float32"))
    net(x)   # materialize deferred-init params off-mesh
    trainer = Trainer(net.collect_params(), "adam",
                      {"learning_rate": 0.01})
    step = trainer.compile_step(lambda a, b: loss(net(a), b))
    with zero_knobs(bucket_bytes=bucket_bytes, min_size=1):
        with make_mesh({"dp": 8}, jax.devices()[:8]) as m:
            xs, ys = shard_batch(x, m), shard_batch(y, m)
            step(xs, ys)
            hlo = step.lower_entry(xs, ys)["lowered"].compile().as_text()
            return aoverlap.overlap_census(hlo, mesh=m)


@pytest.fixture(scope="module")
def serial_census():
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    return _acceptance_census(0)


@pytest.fixture(scope="module")
def bucketed_census():
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    return _acceptance_census(16384)


@needs_mesh
def test_serial_baseline_measures_zero_overlap(serial_census):
    """The monolithic step (one packed collective over every unit)
    leaves nothing independent to hide behind: fraction ~0 at metric
    resolution (the lone residual hider is the nanoseconds-scale loss
    tail the scheduler may park after the weight all-gather)."""
    rep = serial_census
    assert rep.scheduled and rep.n_collectives >= 2
    assert rep.total_comm_s > 0
    assert rep.overlap_fraction < 1e-3, rep.summary_line()
    assert rep.exposed_comm_s >= 0.99 * rep.total_comm_s
    assert "dp" in rep.per_axis_total_s


@needs_mesh
def test_bucketed_step_overlaps_collectives(bucketed_census,
                                            serial_census):
    """The ISSUE 17 acceptance bar: bucket k's all-gather is free to
    run during bucket k+1's optimizer update, and the XLA scheduler
    demonstrably interleaves them — positive measured fraction."""
    rep = bucketed_census
    assert rep.overlap_fraction > 5e-3, rep.summary_line()
    assert rep.overlap_fraction > serial_census.overlap_fraction
    assert rep.n_collectives >= serial_census.n_collectives
    hidden = [w for w in rep.windows
              if w.kind == "all_gather" and w.n_hiders > 0]
    assert hidden, rep.table_str()
    assert all(w.hide_s > 0 for w in hidden)


@pytest.mark.lint
@needs_mesh
def test_overlap_baseline_sweep(serial_census, bucketed_census):
    """The checked-in overlap posture of both legs, enforced against
    tests/fixtures/overlap_baselines.json on every tier-1 run (the
    sharding-baseline sweep's shape, one gate per leg)."""
    baselines = aoverlap.load_baselines(BASELINES)
    for leg, rep in (("zero-serial", serial_census),
                     ("zero-bucketed", bucketed_census)):
        findings = aoverlap.check_baseline(rep, baselines, leg)
        assert findings == [], [str(f) for f in findings]


@needs_mesh
def test_publish_refreshes_exposed_comm_gauges(bucketed_census):
    aoverlap.publish(bucketed_census)
    assert telemetry.value(tn.OVERLAP_FRACTION) == pytest.approx(
        bucketed_census.overlap_fraction)
    assert telemetry.value(tn.SHARDING_EXPOSED_COMM, "dp") == \
        pytest.approx(bucketed_census.per_axis_exposed_s["dp"])


# ---------------------------------------------------------------------------
# ProgramReport / analyze integration (cheap dp=4 toy)
# ---------------------------------------------------------------------------

def _toy_step(seed=3):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4, activation="relu"))
    net.add(nn.Dense(5, in_units=8, activation="relu"))
    net.add(nn.Dense(3, in_units=5))
    net.initialize()
    trainer = Trainer(net.collect_params(), "adam",
                      {"learning_rate": 1e-2})
    loss_blk = gloss.SoftmaxCrossEntropyLoss()
    step = trainer.compile_step(lambda a, b: loss_blk(net(a), b))
    rng = onp.random.RandomState(0)
    x = nd.array(rng.randn(8, 4).astype("float32"))
    y = nd.array(rng.randint(0, 3, size=(8,)).astype("int32"))
    return net, step, x, y


@needs_mesh
def test_program_report_carries_overlap_brief():
    _, step, x, y = _toy_step()
    with zero_knobs(bucket_bytes=16384, min_size=1):
        with make_mesh({"dp": DP}, jax.devices()[:DP]) as mesh:
            xs, ys = shard_batch(x, mesh), shard_batch(y, mesh)
            step(xs, ys)
            rep = step.analyze(xs, ys)
    assert rep.overlap is not None
    assert rep.overlap.total_comm_s > 0
    d = rep.to_dict()
    assert d["overlap"]["n_collectives"] == rep.overlap.n_collectives
    assert "overlap" in rep.summary()


@needs_mesh
def test_env_baseline_gate_fires_through_analyze(monkeypatch,
                                                 tmp_path):
    """MXNET_OVERLAP_BASELINE=<path>:<leg> rides analyze(): a baseline
    demanding an impossible fraction produces the overlap-regression
    finding on the ProgramReport."""
    p = tmp_path / "demanding.json"
    p.write_text(json.dumps({"toy": {"exposed_comm_s": 0.0,
                                     "overlap_fraction": 0.9,
                                     "tol_pct": 1}}))
    monkeypatch.setenv("MXNET_OVERLAP_BASELINE", f"{p}:toy")
    _, step, x, y = _toy_step(seed=5)
    with make_mesh({"dp": DP}, jax.devices()[:DP]) as mesh:
        xs, ys = shard_batch(x, mesh), shard_batch(y, mesh)
        step(xs, ys)
        rep = step.analyze(xs, ys)
    hits = [f for f in rep.findings if f.rule == "overlap-regression"]
    assert hits and any("[toy]" in f.message for f in hits)


# ---------------------------------------------------------------------------
# numerics: bucketed update is BIT-EXACT vs the monolithic baseline
# ---------------------------------------------------------------------------

def _parity_run(opt, kwargs, bucket_bytes, min_size=None, steps=3):
    mx.random.seed(3)
    net = nn.HybridSequential()
    # sizes straddle DP divisibility (weight 15, bias 5) like the
    # canonical zero-shard fixture
    net.add(nn.Dense(8, in_units=4, activation="relu"))
    net.add(nn.Dense(5, in_units=8, activation="relu"))
    net.add(nn.Dense(3, in_units=5))
    net.initialize()
    trainer = Trainer(net.collect_params(), opt, dict(kwargs))
    loss_blk = gloss.SoftmaxCrossEntropyLoss()
    step = trainer.compile_step(lambda a, b: loss_blk(net(a), b))
    rng = onp.random.RandomState(0)
    x = nd.array(rng.randn(8, 4).astype("float32"))
    y = nd.array(rng.randint(0, 3, size=(8,)).astype("int32"))
    losses = []
    with zero_knobs(bucket_bytes=bucket_bytes, min_size=min_size):
        with make_mesh({"dp": DP}, jax.devices()[:DP]) as mesh:
            xs, ys = shard_batch(x, mesh), shard_batch(y, mesh)
            for _ in range(steps):
                losses.append(step(xs, ys).asnumpy())
    assert step.zero_sharded
    params = {k: p.data().asnumpy()
              for k, p in net.collect_params().items()}
    return losses, params


@needs_mesh
@pytest.mark.parametrize("opt,kwargs", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 1e-2}),
])
def test_bucketed_bit_exact_vs_monolithic(opt, kwargs):
    """Bucketing is pure routing: every bucket size — below the
    smallest param, and above the total gradient bytes — trains
    bit-identically to the serial monolithic step."""
    base_l, base_p = _parity_run(opt, kwargs, 0)
    for bb in (16, 1 << 30):
        l, p = _parity_run(opt, kwargs, bb)
        for a, b in zip(base_l, l):
            onp.testing.assert_array_equal(a, b)
        for k in base_p:
            onp.testing.assert_array_equal(base_p[k], p[k], err_msg=k)


@needs_mesh
def test_bucketed_bit_exact_multi_unit_min_size_one():
    """shard_min_size=1 makes EVERY param its own shard unit: several
    buckets of several units each, still bit-exact."""
    base_l, base_p = _parity_run("adam", {"learning_rate": 1e-2}, 0,
                                 min_size=1)
    l, p = _parity_run("adam", {"learning_rate": 1e-2}, 64, min_size=1)
    for a, b in zip(base_l, l):
        onp.testing.assert_array_equal(a, b)
    for k in base_p:
        onp.testing.assert_array_equal(base_p[k], p[k], err_msg=k)


# ---------------------------------------------------------------------------
# transfer guard: the bucketed pipelined hot loop stays sync-free
# ---------------------------------------------------------------------------

@needs_mesh
def test_bucketed_pipelined_loop_zero_unblessed_syncs(monkeypatch):
    """MXNET_TRANSFER_GUARD=raise + a 12-step prefetched run with the
    bucketed zero step: the only host syncs are the blessed window
    retires — bucketing adds no hidden device round-trips."""
    monkeypatch.setenv("MXNET_TRANSFER_GUARD", "raise")
    mx.random.seed(3)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4, activation="relu"))
    net.add(nn.Dense(3, in_units=8))
    net.initialize()
    trainer = Trainer(net.collect_params(), "adam",
                      {"learning_rate": 1e-2})
    loop = TrainLoop(net, trainer, gloss.SoftmaxCrossEntropyLoss(),
                     inflight=2)
    rng = onp.random.RandomState(7)
    x = nd.array(rng.randn(8, 4).astype("float32"))
    y = nd.array(rng.randint(0, 3, size=(8,)).astype("int32"))
    with zero_knobs(bucket_bytes=16384, min_size=1):
        with make_mesh({"dp": DP}, jax.devices()[:DP]):
            tguard.reset_sync_counts()
            tguard.clear_events()
            losses = []
            for bx, by in loop.prefetch((x, y) for _ in range(12)):
                losses.append(loop.step(bx, by))
            loop.synchronize()
    assert loop.compiled_step.zero_sharded
    counts = tguard.sync_counts()
    assert counts.get("wait_to_read", 0) == 0
    assert counts.get("window_retire", 0) == 12
    assert tguard.events() == []
    assert onp.isfinite(losses[-1].asnumpy()).all()


# ---------------------------------------------------------------------------
# double-buffered pipeline permutes (parallel/pipeline.py)
# ---------------------------------------------------------------------------

def _stage(p, x):
    return jnp.tanh(x @ p)


def test_double_buffer_pipeline_bit_exact():
    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 devices")
    from mxnet_tpu.parallel.pipeline import run_pipeline
    pp, d, b, m = 4, 6, 16, 8
    rng = onp.random.RandomState(5)
    stages = jnp.asarray(rng.randn(pp, d, d).astype("float32") * 0.5)
    x = jnp.asarray(rng.randn(b, d).astype("float32"))
    mesh = Mesh(onp.array(jax.devices()[:pp]), ("pp",))
    classic = run_pipeline(_stage, stages, x, m, mesh,
                           double_buffer=False)
    db = run_pipeline(_stage, stages, x, m, mesh, double_buffer=True)
    # one extra slot of latency, identical math: bit-exact outputs
    onp.testing.assert_array_equal(onp.asarray(classic),
                                   onp.asarray(db))


def test_double_buffer_env_default(monkeypatch):
    from mxnet_tpu.parallel import pipeline as pmod
    monkeypatch.delenv("MXNET_PIPELINE_DOUBLE_BUFFER", raising=False)
    assert pmod._double_buffer_default() is False
    for v in ("1", "true", "YES", "on"):
        monkeypatch.setenv("MXNET_PIPELINE_DOUBLE_BUFFER", v)
        assert pmod._double_buffer_default() is True
    for v in ("0", "false", "off", ""):
        monkeypatch.setenv("MXNET_PIPELINE_DOUBLE_BUFFER", v)
        assert pmod._double_buffer_default() is False
