"""Continuous-batching decode engine (docs/SERVING.md "Continuous
batching").

Pins the iteration-level-scheduling contracts:

- fake-clock join/leave: requests enter and exit the running batch
  BETWEEN decode steps, a freed slot is refilled from the queue on the
  next iteration;
- BIT-EXACT token parity: a request decoded continuously next to
  batch-mates produces the identical token sequence it produces alone
  (masked carries + the null page make neighbours invisible);
- chunked prefill never starves the decode batch (strict alternation);
- KV-page exhaustion sheds with a typed ``Overloaded(reason="kvcache")``
  and allocator bytes == census bytes (one accounting path);
- the guarded zero-sync streamed run: 12+ iterations under
  MXNET_TRANSFER_GUARD=raise with the retire as the ONE blessed sync;
- the decode program passes the full static-analysis lint with
  ``predict`` expectations;
- rnn_decode_step interpret-vs-XLA parity across all four cell modes.
"""
import numpy as onp
import pytest

import jax.numpy as jnp

from mxnet_tpu import serving, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import (DecodeEngine, DecodeStream, Overloaded,
                               PagedKVCache, TinyDecoder, pages_needed)
from mxnet_tpu.serving.resilience import (DeadlineExceeded,
                                          ServingShutdown)

VOCAB = 48


@pytest.fixture(scope="module")
def model():
    return TinyDecoder(vocab=VOCAB, d_model=32, num_heads=2, seed=0)


class FakeClock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float):
        self.t += dt


def make_engine(model, **kw):
    kw.setdefault("ladder", (1, 2))
    kw.setdefault("page_size", 4)
    kw.setdefault("max_context", 32)
    kw.setdefault("start", False)
    return DecodeEngine(model, **kw)


def drive(eng, max_iters: int = 200) -> int:
    """Manually run the scheduler to completion (start=False engines)."""
    it = 0
    while it < max_iters:
        did = eng.step_once()
        eng.sync()
        if not did and eng._idle():
            return it
        it += 1
    raise AssertionError(f"engine did not go idle in {max_iters} iters")


def prompt(seed: int, n: int):
    return onp.random.RandomState(seed).randint(
        0, VOCAB, size=n).astype(onp.int32)


# ---------------------------------------------------------------------------
# accessors
# ---------------------------------------------------------------------------

def test_slot_ladder_env_override(monkeypatch):
    monkeypatch.setenv("MXNET_DECODE_SLOTS", "2, 8,4")
    assert serving.slot_ladder() == (2, 4, 8)
    monkeypatch.setenv("MXNET_DECODE_SLOTS", "garbage")
    assert serving.slot_ladder() == serving.decode.DECODE_SLOT_LADDER


def test_page_size_and_chunk_env_overrides(monkeypatch):
    monkeypatch.setenv("MXNET_DECODE_KV_PAGE_SIZE", "8")
    monkeypatch.setenv("MXNET_DECODE_PREFILL_CHUNK", "32")
    assert serving.kv_page_size() == 8
    assert serving.prefill_chunk() == 32


def test_page_size_and_chunk_range_is_clamped(monkeypatch):
    """Both widths are input from outside the program: 1..4096, the
    default when the value does not parse."""
    for var, accessor, default in (
            ("MXNET_DECODE_KV_PAGE_SIZE", serving.kv_page_size,
             serving.kvcache.KV_PAGE_SIZE),
            ("MXNET_DECODE_PREFILL_CHUNK", serving.prefill_chunk,
             serving.decode.PREFILL_CHUNK)):
        for raw, want in (("0", 1), ("-8", 1), ("4096", 4096),
                          ("4097", 4096), ("x", default)):
            monkeypatch.setenv(var, raw)
            assert accessor() == want, (var, raw)


# ---------------------------------------------------------------------------
# paged KV cache
# ---------------------------------------------------------------------------

def test_kvcache_null_page_reserved_and_freelist():
    kv = PagedKVCache(1, 2, 16, num_pages=5, page_size=4)
    assert kv.free_pages() == 4          # page 0 excluded
    a, b = object(), object()
    pa = kv.alloc(a, 3)
    assert pa is not None and 0 not in pa
    assert kv.alloc(b, 2) is None        # only 1 left
    pb = kv.alloc(b, 1)
    assert pb is not None and kv.free_pages() == 0
    assert kv.release(a) == 3
    assert kv.free_pages() == 3
    assert kv.used_pages() == 1 and kv.pages_of(b) == pb


def test_kvcache_reserve_excludes_pages_from_admission():
    kv = PagedKVCache(1, 2, 16, num_pages=5, page_size=4)
    a, b = object(), object()
    assert kv.reserve(a, 3)
    assert not kv.can_reserve(2)         # 4 - 3 reserved = 1 free
    assert not kv.reserve(b, 2)
    pages = kv.alloc(a, 3)               # draws down the reservation
    assert len(pages) == 3 and kv.free_pages() == 1
    assert kv.reserve(b, 1)


def test_kvcache_allocator_bytes_equal_census_bytes():
    """ONE accounting path: the allocator prices its pages with the
    census's device_bytes rule, so the kvcache pool's census bytes grow
    by exactly PagedKVCache.total_bytes()."""
    import gc
    gc.collect()
    census = telemetry.memory.census()
    before = census.live_bytes_by_pool()["kvcache"]
    kv = PagedKVCache(1, 2, 16, num_pages=9, page_size=8)
    after = census.live_bytes_by_pool()["kvcache"]
    assert after - before == kv.total_bytes()
    assert kv.total_bytes() == \
        2 * (9 * 8 * 2 * 16) * 4         # K+V, f32
    assert kv.total_bytes() == kv.bytes_per_page * kv.num_pages


def test_pages_needed():
    assert pages_needed(1, 4) == 1
    assert pages_needed(4, 4) == 1
    assert pages_needed(5, 4) == 2
    assert pages_needed(0, 4) == 1       # floor: every request holds >=1


# ---------------------------------------------------------------------------
# iteration-level scheduling (fake clock, manual drive)
# ---------------------------------------------------------------------------

def test_join_leave_between_steps(model):
    """3 requests, 2 slots: the queued request joins the running batch
    the iteration after a finisher leaves — nobody waits for the whole
    batch to retire."""
    clk = FakeClock()
    eng = make_engine(model, clock=clk)
    try:
        sa = eng.submit(prompt(1, 3), max_new=6)
        sb = eng.submit(prompt(2, 2), max_new=2)
        sc = eng.submit(prompt(3, 2), max_new=3)
        # first refill seats A and B; C waits in the queue
        eng.step_once()
        assert [o is not None for o in eng._occupant] == [True, True]
        assert len(eng._queue) == 1
        joined_at = None
        for it in range(60):
            clk.advance(0.001)
            did = eng.step_once()
            eng.sync()
            occ = [getattr(o, "seq", None) for o in eng._occupant]
            if joined_at is None and 2 in occ:
                joined_at = it
                assert sb.done       # C sits in B's freed slot
            if not did and eng._idle():
                break
        assert joined_at is not None, "queued request never joined"
        assert len(sa.result(0)) == 6
        assert len(sb.result(0)) == 2
        assert len(sc.result(0)) == 3
        assert eng.stats["completed"] == 3
        assert eng.kv.used_pages() == 0 and eng.kv.free_pages() > 0
    finally:
        eng.close()


def test_fake_clock_ttft_and_stream_record(model):
    clk = FakeClock(t=50.0)
    eng = make_engine(model, ladder=(1,), clock=clk)
    try:
        s = eng.submit(prompt(4, 2), max_new=3)
        while not s.done:
            clk.advance(0.25)
            eng.step_once()
            eng.sync()
        rec = s.record()
        assert rec["outcome"] == "ok" and rec["tokens"] == 3
        # prefill(1 chunk) retires 0.25s after submit on the fake clock
        assert rec["ttft_s"] == pytest.approx(0.25)
        assert rec["tpot_s"] == pytest.approx([0.25, 0.25])
        assert s.ttft_s == pytest.approx(0.25)
    finally:
        eng.close()


def test_stream_next_token_iteration_and_result(model):
    eng = make_engine(model, ladder=(1,))
    try:
        s = eng.submit(prompt(5, 2), max_new=4)
        drive(eng)
        toks = [t for t in s]
        assert len(toks) == 4
        assert s.result(0) == toks
        assert s.next_token(0) is None   # cursor stays at end-of-stream
        assert all(0 <= t < VOCAB for t in toks)
    finally:
        eng.close()


def test_eos_frees_slot_early(model):
    """An EOS hit retires the request before max_new and releases its
    pages immediately."""
    eng = make_engine(model, ladder=(1,))
    try:
        s = eng.submit(prompt(6, 3), max_new=20)
        first = None
        while first is None:
            eng.step_once()
            eng.sync()
            r = s.record()
            if r["tokens"]:
                first = r
        # resubmit with eos = the first generated token: exactly 1 token
        drive(eng)
        tok0 = s.result(0)[0]
        s2 = eng.submit(prompt(6, 3), max_new=20, eos=int(tok0))
        drive(eng)
        assert s2.result(0) == [tok0]
        assert eng.kv.used_pages() == 0
    finally:
        eng.close()


def test_deadline_miss_is_typed(model):
    clk = FakeClock()
    eng = make_engine(model, ladder=(1,), clock=clk)
    try:
        s = eng.submit(prompt(7, 2), max_new=8, deadline_ms=100.0)
        clk.advance(10.0)                # way past the deadline
        drive(eng)
        with pytest.raises(DeadlineExceeded):
            s.result(0)
        assert eng.stats["deadline_missed"] == 1
        assert eng.kv.used_pages() == 0  # pages released on failure
    finally:
        eng.close()


def test_drain_sheds_then_close_is_shutdown(model):
    eng = make_engine(model, ladder=(1,))
    try:
        s = eng.submit(prompt(8, 2), max_new=2)
        assert eng.drain()
        assert s.result(0) and s.done
        with pytest.raises(Overloaded) as ei:
            eng.submit(prompt(8, 2))
        assert ei.value.reason == "draining"
    finally:
        eng.close()
    with pytest.raises(ServingShutdown):
        eng.submit(prompt(8, 2))


# ---------------------------------------------------------------------------
# bit-exact token parity: continuous vs single-request
# ---------------------------------------------------------------------------

def test_bit_exact_parity_continuous_vs_single(model):
    """THE correctness pin: a request decoded in a full continuous
    batch (joining/leaving neighbours, shared page pool) emits the
    BIT-identical token sequence it emits running alone — masked
    carries, the null page, and per-slot page tables make batch-mates
    invisible."""
    prompts = [prompt(10, 2), prompt(11, 7), prompt(12, 3),
               prompt(13, 5)]
    mns = [6, 3, 8, 4]
    eng = make_engine(model, ladder=(1, 2, 4), max_context=32)
    try:
        streams = [eng.submit(p, max_new=m)
                   for p, m in zip(prompts, mns)]
        drive(eng)
        batched = [s.result(0) for s in streams]
    finally:
        eng.close()
    single = []
    eng1 = make_engine(model, ladder=(1, 2, 4), max_context=32)
    try:
        for p, m in zip(prompts, mns):
            eng1._draining = False       # sequential: reopen after drain
            s = eng1.submit(p, max_new=m)
            assert eng1.drain()
            single.append(s.result(0))
    finally:
        eng1.close()
    assert batched == single


def test_run_decode_static_and_continuous_same_tokens(model):
    """The bench A/B's honesty condition: both policies run the same
    compiled programs over the same requests — total tokens identical,
    only the schedule differs."""
    prompts = [prompt(20 + i, 2 + (i % 4)) for i in range(6)]
    mns = [5, 2, 3, 2, 4, 2]
    cont = serving.run_decode(model, prompts, mns, ladder=(1, 2),
                              page_size=4, warmup=False)
    stat = serving.run_decode(model, prompts, mns, ladder=(1, 2),
                              page_size=4, static=True, warmup=False)
    assert cont["tokens"] == stat["tokens"] == sum(mns)
    assert cont["mode"] == "continuous" and stat["mode"] == "static"
    for rep in (cont, stat):
        assert rep["ttft_p50_ms"] is not None
        assert rep["tpot_p50_ms"] is not None
        assert rep["decode_tokens_per_sec"] > 0
        assert 0 < rep["kv_page_util"] <= 1.0


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------

def test_chunked_prefill_never_starves_decode(model, monkeypatch):
    """Strict alternation: while decode-ready slots exist, two prefill
    chunks never run back-to-back — a 12-token prompt (3 chunks at
    MXNET_DECODE_PREFILL_CHUNK=4) cannot stall the running batch."""
    monkeypatch.setenv("MXNET_DECODE_PREFILL_CHUNK", "4")
    eng = make_engine(model, ladder=(2,))
    kinds = []
    real_pre, real_dec = eng._dispatch_prefill, eng._dispatch_decode

    def spy_pre(slot):
        dec_ready = [s for s in range(eng.slots)
                     if eng._occupant[s] is not None
                     and eng._occupant[s].phase == "decode"]
        kinds.append(("prefill", bool(dec_ready)))
        return real_pre(slot)

    def spy_dec(slots):
        kinds.append(("decode", True))
        return real_dec(slots)

    eng._dispatch_prefill = spy_pre
    eng._dispatch_decode = spy_dec
    try:
        assert eng._chunk == 4
        s_short = eng.submit(prompt(30, 2), max_new=8)
        s_long = eng.submit(prompt(31, 12), max_new=2)
        drive(eng)
        assert len(s_short.result(0)) == 8
        assert len(s_long.result(0)) == 2
        assert eng.stats["prefill_chunks"] == 1 + 3   # short + 12/4
        for i in range(1, len(kinds)):
            if kinds[i][0] == "prefill" and kinds[i][1]:
                assert kinds[i - 1][0] != "prefill", \
                    "two consecutive prefill chunks starved the " \
                    "decode batch"
    finally:
        eng.close()


def test_prefill_chunk_count_and_positions(model, monkeypatch):
    monkeypatch.setenv("MXNET_DECODE_PREFILL_CHUNK", "4")
    eng = make_engine(model, ladder=(1,))
    try:
        s = eng.submit(prompt(32, 10), max_new=2)    # 10 -> 4+4+2
        drive(eng)
        assert eng.stats["prefill_chunks"] == 3
        assert len(s.result(0)) == 2
        assert eng.stats["steps"] == 1               # 1 decode step
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# admission: KV-page exhaustion
# ---------------------------------------------------------------------------

def test_kv_exhaustion_sheds_typed_overloaded(model):
    # pool sized for ONE request's worst case: the second is shed
    eng = make_engine(model, ladder=(1, 2), num_pages=4, depth=8)
    try:
        s = eng.submit(prompt(40, 3), max_new=6)     # needs 3 pages
        with pytest.raises(Overloaded) as ei:
            eng.submit(prompt(41, 3), max_new=6)
        assert ei.value.reason == "kvcache"
        assert eng.stats["rejected"] == 1
        drive(eng)
        assert len(s.result(0)) == 6                 # victim unharmed
        # pages released at retire: the pool admits again
        s2 = eng.submit(prompt(41, 3), max_new=6)
        drive(eng)
        assert len(s2.result(0)) == 6
    finally:
        eng.close()


def test_oversized_request_is_an_error_not_a_shed(model):
    eng = make_engine(model, max_context=8)
    try:
        with pytest.raises(MXNetError, match="max_context"):
            eng.submit(prompt(42, 6), max_new=6)     # 6+6+1 > 8
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# the guarded zero-sync streamed run
# ---------------------------------------------------------------------------

def test_streamed_run_zero_unblessed_syncs(model, monkeypatch):
    """12+ scheduler iterations under MXNET_TRANSFER_GUARD=raise: the
    retire is the ONE blessed sync; next-step tokens chain device-side,
    so the wait_to_read counter must not move."""
    monkeypatch.setenv("MXNET_TRANSFER_GUARD", "raise")
    eng = make_engine(model, ladder=(1, 2))
    try:
        eng.warmup()
        before = telemetry.value(telemetry.names.HOST_SYNCS,
                                 "wait_to_read") or 0
        streams = [eng.submit(prompt(50 + i, 2 + i), max_new=5 + i)
                   for i in range(3)]
        iters = drive(eng)
        after = telemetry.value(telemetry.names.HOST_SYNCS,
                                "wait_to_read") or 0
        assert iters >= 12
        assert [len(s.result(0)) for s in streams] == [5, 6, 7]
        assert after - before == 0, \
            "decode hot loop performed an unblessed NDArray host sync"
    finally:
        eng.close()


def test_warmup_means_zero_live_traces(model):
    eng = make_engine(model, ladder=(1, 2))
    try:
        exes = eng.warmup()
        assert set(exes) == {("decode", 1), ("decode", 2),
                             ("prefill", 1), ("prefill", 2)}
        assert eng.n_traces == 0
        streams = [eng.submit(prompt(60 + i, 3), max_new=3)
                   for i in range(2)]
        drive(eng)
        for s in streams:
            assert len(s.result(0)) == 3
        assert eng.n_traces == 0, "AOT executables must serve traffic"
    finally:
        eng.close()


def test_aot_drift_is_counted_logged_and_still_served(model, caplog):
    """A warmed-up executable whose signature no longer matches its
    arguments (here: bucket 2's executable under bucket 1's key) is
    dropped and the call re-jits — once per entry, counted and logged
    with the bucket, the stream unchanged."""
    ref = make_engine(model, ladder=(1, 2))
    try:
        s = ref.submit(prompt(70, 3), max_new=4)
        drive(ref)
        want = s.result(0)
    finally:
        ref.close()
    eng = make_engine(model, ladder=(1, 2))
    try:
        exes = eng.warmup()
        entry = eng._entry("decode", 1)
        entry["exe"] = exes[("decode", 2)]
        before = telemetry.value(telemetry.names.DECODE_AOT_FALLBACK)
        with caplog.at_level("WARNING", logger="mxnet_tpu.serving.decode"):
            s = eng.submit(prompt(70, 3), max_new=4)
            drive(eng)
        assert s.result(0) == want
        assert entry["exe"] is None
        assert telemetry.value(telemetry.names.DECODE_AOT_FALLBACK) \
            - before == 1
        warned = [r.getMessage() for r in caplog.records
                  if "AOT" in r.getMessage()]
        assert len(warned) == 1 and "decode" in warned[0] \
            and "slot bucket 1" in warned[0]
        # the prefill bucket never drifted: its executable still serves
        assert eng._entry("prefill", 1)["exe"] is not None
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# static analysis + telemetry
# ---------------------------------------------------------------------------

def test_decode_program_analysis(model):
    eng = make_engine(model)
    try:
        report = eng.analyze()
        assert report.mode == "predict"
        assert report.ok, report.summary()
        assert not report.collectives.ops
        assert report.host_transfers == []
    finally:
        eng.close()


def test_decode_metrics_flow(model):
    reg = telemetry.registry()
    tok0 = reg.value(telemetry.names.DECODE_TOKENS) or 0
    ttft = reg.get(telemetry.names.DECODE_TTFT_SECONDS)
    tpot = reg.get(telemetry.names.DECODE_TPOT_SECONDS)
    ttft0, tpot0 = ttft.count(), tpot.count()
    eng = make_engine(model, ladder=(1, 2))
    try:
        streams = [eng.submit(prompt(70 + i, 2), max_new=3)
                   for i in range(2)]
        drive(eng)
        for s in streams:
            s.result(0)
    finally:
        eng.close()
    assert (reg.value(telemetry.names.DECODE_TOKENS) or 0) - tok0 == 6
    assert ttft.count() - ttft0 == 2     # one first token per request
    assert tpot.count() - tpot0 == 4     # the rest are inter-token gaps
    assert (reg.value(telemetry.names.DECODE_ACTIVE_SLOTS) or 0) == 0
    used = reg.value(telemetry.names.DECODE_KV_PAGES, "used") or 0
    assert used == 0                     # everything released


# ---------------------------------------------------------------------------
# loadgen streaming aggregation
# ---------------------------------------------------------------------------

def test_streaming_summary_percentiles():
    from mxnet_tpu.serving import loadgen
    recs = [{"tokens": 3, "ttft_s": 0.010, "tpot_s": [0.002, 0.002]},
            {"tokens": 2, "ttft_s": 0.030, "tpot_s": [0.004]},
            {"tokens": 0, "ttft_s": None, "tpot_s": []}]
    out = loadgen.streaming_summary(recs, wall=0.5)
    assert out["stream_tokens"] == 5
    assert out["tokens_per_sec"] == pytest.approx(10.0)
    assert out["ttft_p50_ms"] == pytest.approx(20.0)
    assert out["tpot_p50_ms"] == pytest.approx(2.0)
    assert out["ttft_p99_ms"] <= 30.0 + 1e-6


def test_closed_loop_attaches_streaming_stats(model):
    """An issue() that returns DecodeStream.record() gets TTFT/TPOT
    percentiles and tokens_per_sec next to the request-level report."""
    from mxnet_tpu.serving import loadgen
    eng = make_engine(model, ladder=(1, 2), depth=16, start=True)
    try:
        eng.warmup()

        def issue(i):
            s = eng.submit(prompt(80 + i, 2), max_new=3)
            s.result(30.0)
            return s.record()

        rep = loadgen.run_closed_loop(issue, concurrency=2, requests=6)
    finally:
        eng.close()
    assert rep["outcomes"]["ok"] == 6
    assert rep["stream_tokens"] == 18
    assert rep["ttft_p50_ms"] is not None
    assert rep["tpot_p50_ms"] is not None
    assert rep["tokens_per_sec"] > 0


# ---------------------------------------------------------------------------
# the single-step decode kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["lstm", "gru", "rnn_tanh", "rnn_relu"])
def test_rnn_decode_step_interpret_matches_xla(mode, monkeypatch):
    from mxnet_tpu.ops.kernels import rnn_scan as K
    rng = onp.random.RandomState(3)
    S, H = 4, 8
    G = {"lstm": 4, "gru": 3}.get(mode, 1)
    xw = jnp.asarray(rng.randn(S, G * H).astype("float32"))
    h = jnp.asarray(rng.randn(S, H).astype("float32"))
    c = jnp.asarray(rng.randn(S, H).astype("float32"))
    w_hh = jnp.asarray((rng.randn(G * H, H) * 0.3).astype("float32"))
    b_hh = jnp.asarray(rng.randn(G * H).astype("float32"))
    monkeypatch.setenv("MXNET_PALLAS", "off")
    h_x, c_x = K.rnn_decode_step(xw, h, c, w_hh, b_hh, mode)
    monkeypatch.setenv("MXNET_PALLAS", "on")   # interpret on CPU
    h_i, c_i = K.rnn_decode_step(xw, h, c, w_hh, b_hh, mode)
    onp.testing.assert_allclose(onp.asarray(h_x), onp.asarray(h_i),
                                atol=1e-6)
    if mode == "lstm":
        onp.testing.assert_allclose(onp.asarray(c_x), onp.asarray(c_i),
                                    atol=1e-6)
    else:
        assert c_x is None and c_i is None


@pytest.mark.parametrize("mode", ["lstm", "gru"])
def test_decode_step_matches_scan_position(mode, monkeypatch):
    """A token decoded step-by-step is bit-identical to the same
    position inside a full rnn_scan (the decode kernel's correctness
    anchor)."""
    from mxnet_tpu.ops import rnn as rnn_ops
    from mxnet_tpu.ops.kernels import rnn_scan as K
    monkeypatch.setenv("MXNET_PALLAS", "off")
    rng = onp.random.RandomState(5)
    T, N, H = 5, 3, 8
    G = {"lstm": 4, "gru": 3}[mode]
    xw = jnp.asarray(rng.randn(T, N, G * H).astype("float32"))
    h = jnp.zeros((N, H), "float32")
    c = jnp.zeros((N, H), "float32") if mode == "lstm" else None
    w_hh = jnp.asarray((rng.randn(G * H, H) * 0.3).astype("float32"))
    b_hh = jnp.asarray(rng.randn(G * H).astype("float32"))
    ys, h_T, _ = rnn_ops.scan_reference(xw, h, c, w_hh, b_hh, mode)
    for t in range(T):
        h, c = K.rnn_decode_step(xw[t], h, c, w_hh, b_hh, mode)
        onp.testing.assert_allclose(onp.asarray(ys[t]), onp.asarray(h),
                                    atol=1e-6)
    onp.testing.assert_allclose(onp.asarray(h_T), onp.asarray(h),
                                atol=1e-6)


def test_paged_attention_reads_through_page_table():
    """paged_decode_attention over a scattered page layout equals dense
    attention over the gathered history."""
    from mxnet_tpu.ops.attention import paged_decode_attention
    rng = onp.random.RandomState(9)
    S, nH, hd, P, ps = 2, 2, 8, 6, 4
    q = jnp.asarray(rng.randn(S, nH, hd).astype("float32"))
    k_pages = jnp.asarray(rng.randn(P, ps, nH, hd).astype("float32"))
    v_pages = jnp.asarray(rng.randn(P, ps, nH, hd).astype("float32"))
    table = jnp.asarray(onp.array([[3, 1, 0], [5, 2, 4]], onp.int32))
    lengths = jnp.asarray(onp.array([5, 7], onp.int32))
    out = onp.asarray(paged_decode_attention(q, k_pages, v_pages,
                                             table, lengths))
    scale = 1.0 / onp.sqrt(hd)
    for s in range(S):
        hist_k = onp.concatenate(
            [onp.asarray(k_pages[int(p)]) for p in table[s]])
        hist_v = onp.concatenate(
            [onp.asarray(v_pages[int(p)]) for p in table[s]])
        L = int(lengths[s])
        for head in range(nH):
            logits = hist_k[:L, head] @ onp.asarray(q[s, head]) * scale
            w = onp.exp(logits - logits.max())
            w /= w.sum()
            ref = w @ hist_v[:L, head]
            onp.testing.assert_allclose(out[s, head], ref, atol=1e-5)
