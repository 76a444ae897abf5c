"""mx.tuning — the self-tuning performance autopilot
(docs/PERF_NOTES.md "Autotuner").

Pins the autopilot's contracts:

- tunable registry semantics: override > env > default resolution at
  every consumer seam (engine window, ZeRO floor, VMEM budget,
  serving knobs), trial-context restore, validity filtering;
- the search: coordinate descent converges on a planted optimum within
  the trial budget; infeasible and FAULTING candidates (OOM-style
  errors) are scored infeasible without aborting; successive halving
  re-measures survivors on noisy backends; the budget is a hard cap;
- the cache: atomic JSON round-trip (a second construction replays the
  winner with ZERO trials), signature change invalidates, corrupt DB
  files degrade to a re-tune, never a crash;
- the ``off|cached|on`` gate semantics;
- numerics safety: tuned configs are bit-exact on losses vs defaults
  (window depth + kernel block knobs are speed, never math), and the
  timed backend's state snapshot/restore leaves the model untouched;
- the ACCEPTANCE loop: the analytical backend sweeps a real
  ``CompiledTrainStep`` space, persists a winner keyed by the compile
  signature, and a fresh construction under ``MXNET_AUTOTUNE=cached``
  replays it with zero trials and bit-exact losses.
"""
import json
import os

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry, tuning
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import Trainer, TrainLoop, nn
from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
from mxnet_tpu.tuning import (AutotuneCache, MeasureResult, Tunable,
                              cache, measure, search, space)

IN, HIDDEN, CLASSES, BS = 16, 32, 8, 8


@pytest.fixture(autouse=True)
def clean_tuning(monkeypatch):
    """Every test starts with no tuned overrides, a memory-only default
    cache, the env gate off, and zeroed telemetry."""
    monkeypatch.delenv("MXNET_AUTOTUNE", raising=False)
    monkeypatch.delenv("MXNET_AUTOTUNE_CACHE", raising=False)
    monkeypatch.delenv("MXNET_AUTOTUNE_BUDGET_TRIALS", raising=False)
    monkeypatch.delenv("MXNET_AUTOTUNE_BACKEND", raising=False)
    space.clear_overrides()
    telemetry.reset()
    yield
    space.clear_overrides()
    telemetry.reset()


def make_batch(seed=0):
    rs = onp.random.RandomState(seed)
    x = mx.nd.array(rs.randn(BS, IN).astype("float32"))
    y = mx.nd.array(rs.randint(0, CLASSES, size=(BS,)).astype("int32"))
    return x, y


def make_step(hidden=HIDDEN, autotune=None, lr=0.1):
    mx.random.seed(42)
    onp.random.seed(42)
    net = nn.HybridSequential()
    net.add(nn.Dense(hidden, activation="relu", in_units=IN),
            nn.Dense(CLASSES, in_units=hidden))
    net.initialize()
    net(mx.nd.array(onp.zeros((1, IN), "float32")))
    loss = SoftmaxCrossEntropyLoss()
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": lr, "momentum": 0.9},
                      kvstore=None)
    step = trainer.compile_step(lambda a, b: loss(net(a), b),
                                autotune=autotune)
    return step, net, trainer


def make_loop(hidden=HIDDEN, lr=0.1):
    mx.random.seed(42)
    onp.random.seed(42)
    net = nn.HybridSequential()
    net.add(nn.Dense(hidden, activation="relu", in_units=IN),
            nn.Dense(CLASSES, in_units=hidden))
    net.initialize()
    net(mx.nd.array(onp.zeros((1, IN), "float32")))
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": lr, "momentum": 0.9},
                      kvstore=None)
    return TrainLoop(net, trainer, SoftmaxCrossEntropyLoss())


# ---------------------------------------------------------------------------
# space: registry + resolution
# ---------------------------------------------------------------------------

def test_registry_has_every_shipped_tunable():
    space.ensure_registered()
    names = {t.name for t in space.tunables()}
    assert {"engine.inflight_steps", "kernels.vmem_tile_budget",
            "kernels.rnn_block_t", "zero.shard_min_size",
            "serving.max_batch", "serving.batch_timeout_ms"} <= names
    for t in space.tunables():
        assert t.default in t.grid
        assert t.seam
        assert t.scope in ("train", "serving", "both")


def test_resolution_precedence(monkeypatch):
    space.ensure_registered()
    t = space.get("engine.inflight_steps")
    assert t.resolve() == 2                       # shipped default
    monkeypatch.setenv("MXNET_INFLIGHT_STEPS", "5")
    assert t.resolve() == 5                       # env beats default
    space.set_override("engine.inflight_steps", 7)
    assert t.resolve() == 7                       # override beats env
    space.clear_overrides(["engine.inflight_steps"])
    assert t.resolve() == 5


def test_consumer_seams_resolve_overrides(monkeypatch):
    from mxnet_tpu import engine
    from mxnet_tpu.gluon import fused_step
    from mxnet_tpu.ops import kernels
    from mxnet_tpu.serving import batcher
    space.apply_config({"engine.inflight_steps": 6,
                        "zero.shard_min_size": 512,
                        "kernels.vmem_tile_budget": 2 * 1024 * 1024,
                        "serving.max_batch": 16,
                        "serving.batch_timeout_ms": 0.5})
    assert engine.inflight_steps() == 6
    assert fused_step._zero_min_size() == 512
    assert kernels.vmem_tile_budget() == 2 * 1024 * 1024
    assert batcher.max_batch_rows() == 16
    assert batcher.batch_timeout_s() == pytest.approx(0.5e-3)


def test_vmem_accessor_env_and_clamp(monkeypatch):
    from mxnet_tpu.ops import kernels
    assert kernels.vmem_tile_budget() == kernels.VMEM_TILE_BUDGET_BYTES
    monkeypatch.setenv("MXNET_VMEM_TILE_BUDGET", str(8 * 1024 * 1024))
    assert kernels.vmem_tile_budget() == 8 * 1024 * 1024
    # clamped to the physical VMEM above, to 64 KiB below
    space.set_override("kernels.vmem_tile_budget", 10**12)
    assert kernels.vmem_tile_budget() == kernels.VMEM_SCOPED_DEFAULT_BYTES
    space.set_override("kernels.vmem_tile_budget", 1)
    assert kernels.vmem_tile_budget() == 64 * 1024


def test_vmem_budget_feeds_all_four_kernel_sizers():
    """One accessor, four consumers: shrinking the budget shrinks the
    rnn timestep block, the attention head group, and the norm/opt
    row-block caps together."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import kernels
    from mxnet_tpu.ops.attention import _head_group
    from mxnet_tpu.ops.kernels import norm as knorm
    from mxnet_tpu.ops.kernels import opt_update as kopt
    from mxnet_tpu.ops.kernels import rnn_scan as krnn
    big = (kernels.vmem_tile_budget(),
           krnn._vmem_plan(64, 8, 4, 128, 4, False)[0],
           _head_group(8, 128, 128), knorm._budget_rows(128),
           kopt._block_rows_cap())
    space.set_override("kernels.vmem_tile_budget", 64 * 1024)
    small = (kernels.vmem_tile_budget(),
             krnn._vmem_plan(64, 8, 4, 128, 4, False)[0],
             _head_group(8, 128, 128), knorm._budget_rows(128),
             kopt._block_rows_cap())
    assert small[0] < big[0]
    for b, s in zip(big[1:], small[1:]):
        assert s <= b
    assert small[3] < big[3] and small[4] < big[4]


def test_rnn_block_t_tunable_and_interpret_contract():
    """The kernels.rnn_block_t override governs the compiled-TPU block
    size but NOT the interpret parity tier, which stays at block 1 —
    that is what keeps the fp32 forward bit-identical to the scan
    reference (PR 10 contract): the tunable can never change the
    numbers the parity sweep pins."""
    from mxnet_tpu.ops.kernels import rnn_scan as krnn
    args = (64, 8, 4, 128, 4)           # seq, N, gates, Hp, itemsize
    auto = krnn._vmem_plan(*args, False)[0]
    space.set_override("kernels.rnn_block_t", 8)
    assert krnn._vmem_plan(*args, False)[0] == 8
    assert krnn._vmem_plan(*args, True)[0] == 1
    space.set_override("kernels.rnn_block_t", 0)   # 0 = auto
    assert krnn._vmem_plan(*args, False)[0] == auto


def test_trial_context_restores_overrides():
    space.set_override("engine.inflight_steps", 3)
    with space.trial({"engine.inflight_steps": 8,
                      "zero.shard_min_size": 512}):
        assert space.value("engine.inflight_steps") == 8
        assert space.value("zero.shard_min_size") == 512
    assert space.value("engine.inflight_steps") == 3
    assert space.get_override("zero.shard_min_size") == (False, None)


def test_search_space_views_and_signature():
    space.ensure_registered()
    train = tuning.SearchSpace("train")
    serving_sp = tuning.SearchSpace("serving")
    assert {t.name for t in serving_sp} == {"serving.max_batch",
                                            "serving.batch_timeout_ms",
                                            "decode.slot_ladder",
                                            "decode.kv_page_size",
                                            "decode.prefill_chunk",
                                            "decode.spec_k",
                                            "decode.prefix_share"}
    assert not any(t.name.startswith(("serving.", "decode."))
                   for t in train)
    assert train.valid(train.defaults())
    assert not train.valid({"kernels.vmem_tile_budget": 2**40})
    assert train.signature() != serving_sp.signature()
    assert train.signature() == space.space_signature("train")


# ---------------------------------------------------------------------------
# search: planted optimum, infeasibility, budget, halving
# ---------------------------------------------------------------------------

def planted_space():
    tx = Tunable("syn.x", default=3, grid=(1, 2, 3, 4, 5),
                 seam="synthetic")
    ty = Tunable("syn.y", default=5, grid=(1, 2, 3, 4, 5),
                 seam="synthetic")
    return (tx, ty)


class FakeBackend:
    name = "analytical"
    deterministic = True

    def __init__(self, fn):
        self.fn = fn
        self.calls = 0

    def measure(self, config, fidelity=1):
        self.calls += 1
        return MeasureResult(self.fn(config))


def _bowl(c):
    return 1e-3 + 1e-4 * ((c["syn.x"] - 4) ** 2
                          + (c["syn.y"] - 2) ** 2)


def test_search_converges_on_planted_optimum_within_budget():
    backend = FakeBackend(_bowl)
    budget = 16
    res = search.coordinate_search(planted_space(), backend, budget)
    assert res.best_config == {"syn.x": 4, "syn.y": 2}
    assert res.n_trials <= budget
    assert res.improved and res.delta_pct > 0
    assert res.tuned_overrides() == {"syn.x": 4, "syn.y": 2}
    assert res.default_score == pytest.approx(_bowl(
        {"syn.x": 3, "syn.y": 5}))


def test_search_budget_is_a_hard_cap():
    backend = FakeBackend(_bowl)
    res = search.coordinate_search(planted_space(), backend, budget=3)
    assert res.n_trials == 3 and res.exhausted
    # best-so-far is still returned, never an exception
    assert res.best_score <= res.default_score


def test_faulting_candidates_scored_infeasible_not_fatal():
    """An OOM-style failure inside a trial becomes an infeasible score
    via the PR 11 taxonomy; the search completes and the winner comes
    from the surviving candidates."""
    def fn(c):
        if c["syn.x"] == 4:
            raise MXNetError("RESOURCE_EXHAUSTED: out of memory "
                             "allocating 8G")
        return _bowl(c)

    backend = FakeBackend(fn)
    res = search.coordinate_search(planted_space(), backend, budget=32)
    assert res.best_config["syn.x"] != 4          # faulting value lost
    assert res.best_config["syn.y"] == 2
    bad = [t for t in res.trials if not t.result.feasible]
    assert bad and all("oom" in t.result.reason for t in bad)


def test_infeasible_default_recovers_to_feasible_candidate():
    def fn(c):
        if c["syn.x"] == 3:                       # the DEFAULT faults
            raise MXNetError("RESOURCE_EXHAUSTED: oom")
        return _bowl(c)

    res = search.coordinate_search(planted_space(), FakeBackend(fn),
                                   budget=32)
    assert res.best_config["syn.x"] == 4
    assert res.delta_pct is None                  # no default baseline


def test_validity_predicate_filters_before_measuring():
    t = Tunable("syn.v", default=1, grid=(1, 2, 3, 4),
                valid=lambda v, _c: v <= 2, seam="synthetic")
    backend = FakeBackend(lambda c: 1.0 / c["syn.v"])
    res = search.coordinate_search((t,), backend, budget=16)
    assert res.best_config == {"syn.v": 2}        # 3, 4 never measured
    assert all(tr.config["syn.v"] <= 2 for tr in res.trials)


def test_successive_halving_on_noisy_backend():
    """Noisy backends re-measure surviving candidates at doubled
    fidelity; deterministic ones measure each candidate exactly once."""
    class Noisy(FakeBackend):
        deterministic = False

    t = Tunable("syn.x", default=1, grid=(1, 2, 3, 4, 5, 6, 7, 8),
                seam="synthetic")
    backend = Noisy(lambda c: 1e-3 + 1e-4 * abs(c["syn.x"] - 6))
    res = search.coordinate_search((t,), backend, budget=64)
    assert res.best_config == {"syn.x": 6}
    assert max(tr.fidelity for tr in res.trials) >= 2   # rungs climbed
    det = FakeBackend(lambda c: 1e-3 + 1e-4 * abs(c["syn.x"] - 6))
    res2 = search.coordinate_search((t,), det, budget=64)
    assert all(tr.fidelity == 1 for tr in res2.trials)
    assert det.calls == len({tuple(sorted(tr.config.items()))
                             for tr in res2.trials})


# ---------------------------------------------------------------------------
# cache: round-trip, invalidation, corruption
# ---------------------------------------------------------------------------

def test_cache_atomic_roundtrip(tmp_path):
    db = AutotuneCache(str(tmp_path / "at.json"))
    db.put("k1", {"config": {"a.b": 1}, "trials": 5})
    fresh = AutotuneCache(str(tmp_path / "at.json"))
    assert fresh.get("k1")["config"] == {"a.b": 1}
    assert fresh.get("nope") is None
    doc = json.loads((tmp_path / "at.json").read_text())
    assert doc["schema"] == cache.CACHE_SCHEMA


def test_cache_corrupt_file_degrades_to_retune(tmp_path):
    p = tmp_path / "at.json"
    p.write_text("{ not json !!!")
    db = AutotuneCache(str(p))
    assert db.get("k1") is None                   # no raise
    db.put("k1", {"config": {}})                  # rewrites cleanly
    assert AutotuneCache(str(p)).get("k1") == {"config": {}}


def test_step_signature_stable_and_shape_sensitive():
    step1, _, _ = make_step()
    step2, _, _ = make_step()
    x, y = make_batch()
    assert cache.step_signature(step1, (x, y)) \
        == cache.step_signature(step2, (x, y))
    # a different model is a different program: the key must move
    step3, _, _ = make_step(hidden=HIDDEN * 2)
    assert cache.step_signature(step1, (x, y)) \
        != cache.step_signature(step3, (x, y))
    # and a different input bucket too
    x2 = mx.nd.array(onp.zeros((BS * 2, IN), "float32"))
    y2 = mx.nd.array(onp.zeros((BS * 2,), "int32"))
    assert cache.step_signature(step1, (x, y)) \
        != cache.step_signature(step1, (x2, y2))


def test_signature_change_invalidates_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_AUTOTUNE_CACHE",
                       str(tmp_path / "at.json"))
    # cache-keying semantics only — a tiny search budget keeps the
    # three full searches cheap without touching what's asserted
    monkeypatch.setenv("MXNET_AUTOTUNE_BUDGET_TRIALS", "8")
    x, y = make_batch()
    step, _, _ = make_step(autotune="on")
    step(x, y)
    assert step.autotune_result.source == "search"
    space.clear_overrides()
    # same program, fresh construction: HIT
    step2, _, _ = make_step(autotune="on")
    step2(x, y)
    assert step2.autotune_result.source == "cache"
    assert step2.autotune_result.trials == 0
    space.clear_overrides()
    # different program: MISS -> its own search
    step3, _, _ = make_step(hidden=HIDDEN * 2, autotune="on")
    step3(x, y)
    assert step3.autotune_result.source == "search"
    assert step3.autotune_result.key != step2.autotune_result.key


# ---------------------------------------------------------------------------
# gate semantics
# ---------------------------------------------------------------------------

def test_autotune_mode_parsing(monkeypatch):
    assert tuning.autotune_mode() == "off"
    for v, want in (("on", "on"), ("1", "on"), ("true", "on"),
                    ("cached", "cached"), ("CACHED", "cached"),
                    ("off", "off"), ("0", "off"), ("", "off"),
                    ("bogus", "off")):
        monkeypatch.setenv("MXNET_AUTOTUNE", v)
        assert tuning.autotune_mode() == want, v
    # the explicit kwarg wins over the env
    monkeypatch.setenv("MXNET_AUTOTUNE", "on")
    assert tuning.autotune_mode("off") == "off"
    assert tuning.autotune_mode(True) == "on"
    assert tuning.autotune_mode(False) == "off"


def test_gate_off_does_nothing():
    x, y = make_batch()
    step, _, _ = make_step()                      # env gate off
    step(x, y)
    out = step.autotune_result
    assert out.mode == "off" and out.trials == 0
    assert space.overrides() == {}
    assert telemetry.value(telemetry.names.AUTOTUNE_CACHE_MISSES) == 0


def test_gate_cached_miss_runs_defaults_zero_trials(tmp_path,
                                                    monkeypatch):
    monkeypatch.setenv("MXNET_AUTOTUNE_CACHE",
                       str(tmp_path / "at.json"))
    x, y = make_batch()
    step, _, _ = make_step(autotune="cached")
    step(x, y)
    out = step.autotune_result
    assert out.source == "default" and out.trials == 0
    assert out.config == {}
    assert space.overrides() == {}                # defaults untouched
    assert not (tmp_path / "at.json").exists()    # nothing persisted
    assert telemetry.value(telemetry.names.AUTOTUNE_CACHE_MISSES) == 1
    assert telemetry.value(telemetry.names.AUTOTUNE_TRIALS,
                           "analytical") == 0


def test_gate_on_searches_within_budget(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_AUTOTUNE_CACHE",
                       str(tmp_path / "at.json"))
    monkeypatch.setenv("MXNET_AUTOTUNE_BUDGET_TRIALS", "5")
    x, y = make_batch()
    step, _, _ = make_step(autotune="on")
    step(x, y)
    out = step.autotune_result
    assert out.source == "search" and 1 <= out.trials <= 5
    assert out.backend == "analytical"            # CPU auto-selects
    assert (tmp_path / "at.json").exists()
    assert telemetry.value(telemetry.names.AUTOTUNE_TRIALS,
                           "analytical") == out.trials


def test_explicit_autotune_method_and_outcome_record(tmp_path,
                                                     monkeypatch):
    monkeypatch.setenv("MXNET_AUTOTUNE_CACHE",
                       str(tmp_path / "at.json"))
    # outcome-record plumbing only — a tiny search budget suffices
    monkeypatch.setenv("MXNET_AUTOTUNE_BUDGET_TRIALS", "8")
    x, y = make_batch()
    step, _, _ = make_step()
    out = step.autotune(x, y, mode="on")
    assert out is step.autotune_result
    assert out.source == "search"
    d = out.bench_dict()
    assert set(d) == {"autotune_config", "autotune_trials",
                      "autotune_delta_pct"}
    assert tuning.last_outcome() is out
    # the subsequent first step call does NOT re-tune
    before = telemetry.value(telemetry.names.AUTOTUNE_TRIALS,
                             "analytical")
    step(x, y)
    assert telemetry.value(telemetry.names.AUTOTUNE_TRIALS,
                           "analytical") == before


# ---------------------------------------------------------------------------
# numerics safety
# ---------------------------------------------------------------------------

def run_trajectory(config=None, steps=6):
    """Loss trajectory of the canonical seeded TrainLoop under a tuned
    config (None = shipped defaults)."""
    space.clear_overrides()
    if config:
        space.apply_config(config)
    try:
        loop = make_loop()
        x, y = make_batch()
        losses = [loop.step(x, y) for _ in range(steps)]
        loop.synchronize()
        return [float(l._data.mean()) for l in losses]
    finally:
        space.clear_overrides()


def test_tuned_configs_are_bit_exact_on_losses():
    """Tunables change SPEED, never numerics: the window-depth and
    kernel-block knobs at non-default values produce bit-identical
    loss trajectories (window parity pinned since PR 5; the rnn block
    tunable cannot leak into the CPU reference path by construction)."""
    base = run_trajectory(None)
    tuned = run_trajectory({"engine.inflight_steps": 4,
                            "kernels.rnn_block_t": 8,
                            "kernels.vmem_tile_budget": 1024 * 1024})
    assert tuned == base
    sync = run_trajectory({"engine.inflight_steps": 0})
    assert sync == base


def test_timed_backend_restores_train_state(tmp_path, monkeypatch):
    """Timed trials execute real steps; the orchestrator's
    capture/apply_train_state bracket must leave params, optimizer
    state and counters exactly where they started."""
    monkeypatch.setenv("MXNET_AUTOTUNE_BACKEND", "timed")
    monkeypatch.setenv("MXNET_AUTOTUNE_BUDGET_TRIALS", "4")
    monkeypatch.setenv("MXNET_AUTOTUNE_CACHE",
                       str(tmp_path / "at.json"))
    x, y = make_batch()
    step, net, trainer = make_step()
    params = list(net.collect_params().values())
    before = [onp.asarray(p._data._data) for p in params]
    n_before = trainer._optimizer.num_update
    out = tuning.tune_step(step, (x, y), mode="on")
    assert out.source == "search" and out.backend == "timed"
    assert trainer._optimizer.num_update == n_before
    for p, b in zip(params, before):
        onp.testing.assert_array_equal(onp.asarray(p._data._data), b)
    # and the tuned step still trains bit-exactly vs an untouched one
    space.clear_overrides()
    ref_step, _, _ = make_step()
    l_ref = float(ref_step(x, y)._data.mean())
    l_tuned = float(step(x, y)._data.mean())
    assert l_tuned == l_ref


# ---------------------------------------------------------------------------
# serving scope
# ---------------------------------------------------------------------------

def make_predictor():
    mx.random.seed(11)
    onp.random.seed(11)
    net = nn.HybridSequential()
    net.add(nn.Dense(HIDDEN, activation="relu", in_units=IN),
            nn.Dense(CLASSES, in_units=HIDDEN))
    net.initialize()
    net(mx.nd.array(onp.zeros((1, IN), "float32")))
    from mxnet_tpu import serving
    return serving.CompiledPredictor(net, bucket_sizes=(1, 2, 4, 8))


def test_predictor_warmup_autotune_and_bucket_feasibility(tmp_path,
                                                          monkeypatch):
    """warmup(autotune='on') sweeps the serving knobs; max_batch
    candidates over the largest bucket are infeasible (bucket_for
    raises inside the trial) and the winner respects the ladder. The
    tuned overrides govern a batcher constructed afterwards."""
    monkeypatch.setenv("MXNET_AUTOTUNE_CACHE",
                       str(tmp_path / "at.json"))
    from mxnet_tpu.serving import batcher
    pred = make_predictor()
    x1 = mx.nd.array(onp.zeros((1, IN), "float32"))
    pred.warmup(x1, autotune="on")
    out = pred.autotune_result
    assert out is not None and out.source == "search"
    applied_max = space.value("serving.max_batch")
    assert applied_max <= 8                       # largest bucket
    assert batcher.max_batch_rows() == applied_max
    rec = tuning.default_cache().get(out.key)
    bad = [t for t in rec["trial_log"] if not t["feasible"]]
    assert bad                                    # 16/32/64 infeasible
    # replay: fresh predictor, cached gate, zero trials, same config
    space.clear_overrides()
    telemetry.reset()
    pred2 = make_predictor()
    pred2.warmup(x1, autotune="cached")
    assert pred2.autotune_result.source == "cache"
    assert pred2.autotune_result.trials == 0
    assert space.value("serving.max_batch") == applied_max


def test_train_and_serving_scopes_do_not_cross(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_AUTOTUNE_CACHE",
                       str(tmp_path / "at.json"))
    # scope filtering only — any search size proves it
    monkeypatch.setenv("MXNET_AUTOTUNE_BUDGET_TRIALS", "8")
    x, y = make_batch()
    step, _, _ = make_step(autotune="on")
    step(x, y)
    tuned = step.autotune_result.config
    assert not any(k.startswith("serving.") for k in tuned)


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def test_autotune_metric_flow(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_AUTOTUNE_CACHE",
                       str(tmp_path / "at.json"))
    # metric plumbing only (trials counter, active-config gauges,
    # hit/miss counters) — a tiny search budget keeps it cheap
    monkeypatch.setenv("MXNET_AUTOTUNE_BUDGET_TRIALS", "8")
    x, y = make_batch()
    step, _, _ = make_step(autotune="on")
    step(x, y)
    n = telemetry.value(telemetry.names.AUTOTUNE_TRIALS, "analytical")
    assert n == step.autotune_result.trials >= 1
    assert telemetry.value(telemetry.names.AUTOTUNE_CACHE_MISSES) == 1
    for name, v in step.autotune_result.config.items():
        g = telemetry.value(telemetry.names.AUTOTUNE_ACTIVE, name)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            assert g == float(v)
        else:
            assert g is not None
    space.clear_overrides()
    step2, _, _ = make_step(autotune="cached")
    step2(x, y)
    assert telemetry.value(telemetry.names.AUTOTUNE_CACHE_HITS) == 1


# ---------------------------------------------------------------------------
# ACCEPTANCE: the deterministic closed loop, end to end
# ---------------------------------------------------------------------------

def test_closed_loop_end_to_end_cpu(tmp_path, monkeypatch):
    """The tier-1 acceptance loop on CPU: (1) the analytical backend
    sweeps a REAL CompiledTrainStep's tunable space and persists a
    winner keyed by the compile signature; (2) a fresh construction —
    new net, new trainer, new step, overrides cleared, exactly what a
    restarted process rebuilds (the signature hashes only process-
    independent facts; tests above pin cross-construction equality) —
    under MXNET_AUTOTUNE=cached replays it with ZERO trials; (3) the
    replayed config trains BIT-EXACTLY like the defaults."""
    db_path = tmp_path / "autotune.json"
    monkeypatch.setenv("MXNET_AUTOTUNE_CACHE", str(db_path))
    x, y = make_batch()

    # ---- defaults trajectory (gate off), the numerics reference
    losses_default = run_trajectory(None)

    # ---- phase 1: tune (mode=on) — search runs, winner persists
    monkeypatch.setenv("MXNET_AUTOTUNE", "on")
    loop = make_loop()
    loop.step(x, y)
    loop.synchronize()
    out1 = loop.compiled_step.autotune_result
    assert out1.source == "search" and out1.trials >= 1
    assert out1.backend == "analytical"
    assert db_path.exists()
    doc = json.loads(db_path.read_text())
    assert list(doc["entries"]) == [out1.key]
    persisted = doc["entries"][out1.key]["config"]
    assert persisted == out1.config
    # the analytical model prefers deeper pipelining: a genuinely
    # non-default winner proves the sweep moved something
    assert persisted, "search should tune at least one knob"

    # ---- phase 2: fresh construction, cached gate -> zero trials
    space.clear_overrides()
    telemetry.reset()
    monkeypatch.setenv("MXNET_AUTOTUNE", "cached")
    loop2 = make_loop()
    x2, y2 = make_batch()
    losses_replay = []
    for _ in range(6):
        losses_replay.append(loop2.step(x2, y2))
    loop2.synchronize()
    out2 = loop2.compiled_step.autotune_result
    assert out2.source == "cache" and out2.trials == 0
    assert out2.config == persisted
    assert space.overrides() == persisted         # config is LIVE
    assert telemetry.value(telemetry.names.AUTOTUNE_TRIALS,
                           "analytical") == 0
    assert telemetry.value(telemetry.names.AUTOTUNE_CACHE_HITS) == 1

    # ---- phase 3: bit-exact losses vs the defaults
    # (loop2's first step ran inside phase 2; its trajectory includes
    # it — compare the full 6-step trajectories)
    losses_replay = [float(l._data.mean()) for l in losses_replay]
    assert losses_replay == losses_default
