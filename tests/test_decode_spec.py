"""Speculative decode + shared prefix cache (docs/SERVING.md
"Speculative decode & prefix sharing").

Pins the PR's non-negotiable contracts:

- BIT-EXACT speculation: a spec-decoded stream emits the identical
  token sequence plain greedy decode emits — across slot ladders,
  spec_k widths, mid-stream joins/leaves, and both decode models
  (RNN + GQA transformer);
- acceptance can only shorten steps: tokens/step > 1.3 on the
  repeated-suffix workload the drafter is built for;
- hash-collision safety: a constant prefix hash may cause lookups to
  scan, never to alias two different prefixes (byte verification);
- COW concurrent divergence: two requests writing into the same
  shared partial page diverge without corrupting each other;
- refcount-exact frees: shed/EOS returns exactly the private tail; a
  shared page frees with its LAST holder and its registry entries die
  with it;
- ~1/N physical pages for N requests over one shared prefix, and
  allocator bytes == census bytes throughout (one accounting path);
- the guarded zero-sync run: 12+ spec+shared iterations under
  MXNET_TRANSFER_GUARD=raise with retire as the ONE blessed sync;
- verify programs AOT-compile at warmup (no live traces under load);
- GQA: the broadcast attention matches an explicit repeated-KV
  reference and the engine sizes the cache by num_kv_heads.
"""
import numpy as onp
import pytest

import jax.numpy as jnp

from mxnet_tpu import serving, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import (DecodeEngine, NgramDrafter, PagedKVCache,
                               TinyDecoder, pages_needed)
from mxnet_tpu.serving import kvcache as kvcache_mod

VOCAB = 48


class FakeClock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float):
        self.t += dt


@pytest.fixture(scope="module")
def model():
    return TinyDecoder(vocab=VOCAB, d_model=32, num_heads=2, seed=0)


@pytest.fixture(scope="module")
def gqa_model():
    from mxnet_tpu.gluon import GQADecoder
    return GQADecoder(vocab=VOCAB, d_model=16, num_heads=4,
                      num_kv_heads=2, num_layers=2, seed=1)


def make_engine(model, **kw):
    kw.setdefault("ladder", (1, 2))
    kw.setdefault("page_size", 4)
    kw.setdefault("max_context", 64)
    kw.setdefault("num_pages", 96)
    kw.setdefault("start", False)
    kw.setdefault("spec_k", 0)
    kw.setdefault("prefix_share", False)
    return DecodeEngine(model, **kw)


def drive(eng, max_iters: int = 400) -> int:
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


def decode_all(model, prompts, mns, **kw):
    eng = make_engine(model, **kw)
    try:
        streams = [eng.submit(p, max_new=m)
                   for p, m in zip(prompts, mns)]
        drive(eng)
        return [s.result(0) for s in streams]
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# drafters
# ---------------------------------------------------------------------------

def test_ngram_drafter_prompt_lookup():
    d = NgramDrafter(n=2)
    # last bigram (3, 4) occurred earlier, followed by 5, 6
    assert d.propose([1, 2, 3, 4, 5, 6, 9, 3, 4], 2) == [5, 6]
    # most RECENT earlier occurrence wins
    assert d.propose([3, 4, 7, 3, 4, 8, 3, 4], 1) == [8]
    # falls back to shorter n-grams before giving up
    assert d.propose([5, 1, 9, 9, 2, 1], 1) == [9]
    assert d.propose([1, 2, 3], 0) == []
    # no earlier occurrence of any suffix -> nothing proposed
    assert d.propose([1, 2, 3, 4], 3) == []


def test_ngram_drafter_k_caps_proposal():
    d = NgramDrafter(n=1)
    hist = [7, 1, 2, 3, 4, 7]
    assert d.propose(hist, 2) == [1, 2]
    assert len(d.propose(hist, 10)) <= 10


# ---------------------------------------------------------------------------
# bit-exact speculation
# ---------------------------------------------------------------------------

_GREEDY = {}


def greedy_baseline(model, prompts, mns, ladder):
    if ladder not in _GREEDY:
        _GREEDY[ladder] = decode_all(model, prompts, mns,
                                     ladder=ladder)
    return _GREEDY[ladder]


@pytest.mark.parametrize("ladder,spec_k",
                         [((1,), 1), ((1, 2), 3), ((1, 2, 4), 6)])
def test_spec_bitexact_across_ladders(model, ladder, spec_k):
    """The pinned contract: speculative streams emit token sequences
    BIT-identical to plain greedy decode, for every ladder bucket and
    draft width — requests outnumber slots so slots join/leave
    mid-run."""
    prompts = [prompt(10 + i, 2 + (i % 5)) for i in range(5)]
    mns = [6, 11, 4, 9, 7]
    greedy = greedy_baseline(model, prompts, mns, ladder)
    spec = decode_all(model, prompts, mns, ladder=ladder,
                      spec_k=spec_k)
    assert spec == greedy


def test_spec_bitexact_midstream_joins_and_leaves(model):
    """Requests submitted WHILE earlier ones are mid-decode (and
    finishing at different times) still stream bit-exact sequences.
    The baseline is the cached batch-submitted greedy run: neither
    speculation nor submit staggering may change a single token."""
    prompts = [prompt(10 + i, 2 + (i % 5)) for i in range(5)]
    mns = [6, 11, 4, 9, 7]
    eng = make_engine(model, ladder=(1, 2, 4), spec_k=4)
    try:
        streams = [eng.submit(prompts[0], max_new=mns[0]),
                   eng.submit(prompts[1], max_new=mns[1])]
        for _ in range(6):                # both mid-flight
            eng.step_once()
            eng.sync()
        streams.append(eng.submit(prompts[2], max_new=mns[2]))
        for _ in range(4):
            eng.step_once()
            eng.sync()
        streams += [eng.submit(p, max_new=m)
                    for p, m in zip(prompts[3:], mns[3:])]
        drive(eng)
        got = [s.result(0) for s in streams]
    finally:
        eng.close()
    assert got == greedy_baseline(model, prompts, mns, (1, 2, 4))


def test_spec_emits_multitoken_steps_on_repetitive_output(model):
    """tokens/step > 1.3 on the repeated-suffix workload (the engine's
    greedy output cycles, which prompt-lookup drafting predicts
    exactly after a warm-up prefix)."""
    prompts = [prompt(60 + i, 4) for i in range(3)]
    res = serving.run_decode(model, prompts, 24, ladder=(1, 2, 4),
                             page_size=4, spec_k=4,
                             prefix_share=False, warmup=False)
    assert res["spec_drafted"] > 0 and res["spec_accepted"] > 0
    tps = res["tokens_per_step"]["mean"]
    assert tps > 1.3, f"tokens/step {tps} <= 1.3"
    assert res["acceptance_rate"] is not None
    # steps can only SHRINK vs greedy, never tokens
    greedy = serving.run_decode(model, prompts, 24, ladder=(1, 2, 4),
                                page_size=4, spec_k=0,
                                prefix_share=False, warmup=False)
    assert res["tokens"] == greedy["tokens"]


def test_spec_stream_record_and_loadgen_summary(model):
    from mxnet_tpu.serving import loadgen
    eng = make_engine(model, spec_k=3)
    try:
        s = eng.submit(prompt(70, 4), max_new=10)
        drive(eng)
        rec = s.record()
    finally:
        eng.close()
    # the first token lands at prefill retire; every later one is a
    # verify step, so step_tokens accounts for exactly tokens - 1
    assert rec["tokens"] == 10
    assert sum(rec["step_tokens"]) == rec["tokens"] - 1
    assert rec["spec_accepted"] <= rec["spec_drafted"]
    summ = loadgen.streaming_summary([rec], 1.0)
    assert "tokens_per_step" in summ
    assert summ["tokens_per_step"]["mean"] == pytest.approx(
        sum(rec["step_tokens"]) / len(rec["step_tokens"]), rel=1e-6)
    if rec["spec_drafted"]:
        assert summ["acceptance_rate"] == pytest.approx(
            rec["spec_accepted"] / rec["spec_drafted"], rel=1e-6)
    # plain-greedy records leave the spec view out entirely
    assert "tokens_per_step" not in loadgen.streaming_summary(
        [{"tokens": 3, "ttft_s": 0.1, "tpot_s": [0.01]}], 1.0)


def test_verify_program_aot_compiled_at_warmup(model):
    eng = make_engine(model, ladder=(1, 2), spec_k=2)
    try:
        exes = eng.warmup()
        assert set(exes) == {("decode", 1), ("decode", 2),
                             ("prefill", 1), ("prefill", 2),
                             ("verify", 1), ("verify", 2)}
        assert eng.n_traces == 0
        streams = [eng.submit(prompt(80 + i, 3), max_new=6)
                   for i in range(2)]
        drive(eng)
        for s in streams:
            assert len(s.result(0)) == 6
        assert eng.n_traces == 0, "verify must serve from AOT"
    finally:
        eng.close()


def test_spec_accounting_and_accept_hist(model):
    eng = make_engine(model, spec_k=4)
    try:
        s = eng.submit(prompt(90, 4), max_new=12)
        drive(eng)
        assert len(s.result(0)) == 12
        st = eng.stats
        assert st["spec_steps"] > 0
        assert st["spec_accepted"] <= st["spec_drafted"]
        hist = st["accept_hist"]
        assert sum(hist.values()) == st["spec_steps"]
        # each step accepts its block of a = accepted-drafts + 1 tokens
        assert sum(n * c for n, c in hist.items()) == \
            st["spec_accepted"] + st["spec_steps"]
        assert all(1 <= n <= 5 for n in hist)
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# knobs
# ---------------------------------------------------------------------------

def test_spec_env_overrides(monkeypatch):
    monkeypatch.setenv("MXNET_DECODE_SPEC_K", "6")
    monkeypatch.setenv("MXNET_DECODE_PREFIX_SHARE", "0")
    assert serving.spec_k() == 6
    assert serving.prefix_share() is False
    monkeypatch.setenv("MXNET_DECODE_SPEC_K", "garbage")
    assert serving.spec_k() == serving.decode.SPEC_K


def test_spec_k_range_is_clamped(monkeypatch):
    """The draft width is input from outside the program: 0..64."""
    for raw, want in (("-1", 0), ("0", 0), ("8", 8), ("64", 64),
                      ("65", 64)):
        monkeypatch.setenv("MXNET_DECODE_SPEC_K", raw)
        assert serving.spec_k() == want, raw


def test_engine_reads_spec_env(monkeypatch, model):
    monkeypatch.setenv("MXNET_DECODE_SPEC_K", "3")
    monkeypatch.setenv("MXNET_DECODE_PREFIX_SHARE", "0")
    eng = make_engine(model, spec_k=None, prefix_share=None)
    try:
        assert eng._spec_k == 3 and eng._prefix_share is False
        assert isinstance(eng._drafter, NgramDrafter)
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# prefix cache: allocator-level contracts
# ---------------------------------------------------------------------------

def test_share_refcounts_and_last_holder_frees():
    kv = PagedKVCache(1, 2, 16, num_pages=8, page_size=4)
    a, b = object(), object()
    pa = kv.alloc(a, 3)
    kv.register_prefix([1, 2, 3, 4, 5], 5, pa[:2])
    kv.share(b, pa[:2])
    kv.alloc(b, 1)
    assert kv.used_pages() == 4          # physical: shared counted once
    assert kv.logical_pages() == 6       # per-holder view
    assert kv.shared_pages() == 2
    assert kv.release(a) == 1            # only a's private page frees
    assert kv.used_pages() == 3
    assert kv.prefix_entries() == 1      # entry survives with b
    assert kv.release(b) == 3            # last holder frees the rest
    assert kv.used_pages() == 0 and kv.free_pages() == 7
    assert kv.prefix_entries() == 0, \
        "registry entries must die with their last page holder"


def test_share_rejects_unallocated_page():
    kv = PagedKVCache(1, 2, 16, num_pages=8, page_size=4)
    with pytest.raises(MXNetError, match="not allocated"):
        kv.share(object(), [3])


def test_cow_swaps_page_and_drops_refcount():
    kv = PagedKVCache(1, 2, 16, num_pages=8, page_size=4)
    a, b = object(), object()
    (p,) = kv.alloc(a, 1)
    kv.k_pages._data = kv.k_pages._data.at[:, p].set(7.0)
    kv.share(b, [p])
    assert kv.page_shared(p)
    new = kv.cow(b, p)
    assert new != p and not kv.page_shared(p)
    assert kv.pages_of(b) == [new] and kv.pages_of(a) == [p]
    assert kv.cow_copies == 1
    # the copy carries the page CONTENT
    assert float(jnp.max(jnp.abs(
        kv.k_pages._data[:, new] - kv.k_pages._data[:, p]))) == 0.0


def test_lookup_prefix_byte_verifies_under_hash_collision(monkeypatch):
    """A constant hash maps every prefix to one bucket; byte
    verification alone must keep lookups exact."""
    monkeypatch.setattr(kvcache_mod, "prefix_hash", lambda toks: 7)
    kv = PagedKVCache(1, 2, 16, num_pages=8, page_size=4)
    a, b = object(), object()
    pa = kv.alloc(a, 2)
    pb = kv.alloc(b, 2)
    kv.register_prefix([1, 2, 3, 4, 5], 5, pa)
    kv.register_prefix([9, 8, 7, 6, 5], 5, pb)
    hit = kv.lookup_prefix(onp.asarray([1, 2, 3, 4, 5, 6]))
    assert hit is not None and hit.pages == tuple(pa)
    hit = kv.lookup_prefix(onp.asarray([9, 8, 7, 6, 5, 1]))
    assert hit is not None and hit.pages == tuple(pb)
    assert kv.lookup_prefix(onp.asarray([1, 2, 3, 9, 5, 6])) is None


def test_engine_bitexact_under_hash_collision(model, monkeypatch):
    """End-to-end collision drill: every prefix hashes identically and
    shared-prefix decode output must still match the no-share run."""
    base = prompt(100, 9)

    def run(share):
        eng = make_engine(model, ladder=(1, 2), spec_k=0,
                          prefix_share=share)
        try:
            s1 = eng.submit(base, max_new=10)
            for _ in range(4):
                eng.step_once()
                eng.sync()
            s2 = eng.submit(onp.concatenate([base, [3, 4]]),
                            max_new=8)
            drive(eng)
            hits = eng.stats["prefix_hits"]
            return [s1.result(0), s2.result(0)], hits
        finally:
            eng.close()

    expect, _ = run(False)
    monkeypatch.setattr(kvcache_mod, "prefix_hash", lambda toks: 7)
    got, hits = run(True)
    assert got == expect
    assert hits >= 1, "byte-equal prefix must still hit under collision"


# ---------------------------------------------------------------------------
# prefix cache: engine-level contracts
# ---------------------------------------------------------------------------

def with_tail(base, tail):
    return onp.concatenate(
        [base, onp.asarray(tail, onp.int32)]).astype(onp.int32)


def shared_run(model, base, tails, mns, *, share, spec_k=0,
               warm_iters=4, ladder=(1, 2, 4), stats_out=None):
    """Donor decodes over ``base + tails[0]``; joiners (submitted only
    after the donor's prefill retires and registers its prompt in the
    content-hash registry) extend the same prefix."""
    eng = make_engine(model, ladder=ladder, spec_k=spec_k,
                      prefix_share=share)
    try:
        streams = [eng.submit(with_tail(base, tails[0]),
                              max_new=mns[0])]
        for _ in range(warm_iters):      # register the donor's prefix
            eng.step_once()
            eng.sync()
        streams += [eng.submit(with_tail(base, t), max_new=m)
                    for t, m in zip(tails[1:], mns[1:])]
        drive(eng)
        if stats_out is not None:
            stats_out.update(eng.stats)
            stats_out["kv"] = eng.kv.stats()
        return [s.result(0) for s in streams]
    finally:
        eng.close()


def test_prefix_share_bitexact_with_rnn_state_resume(model):
    """A joiner seated mid-prefix resumes from the donor's recurrent
    state snapshot — output must be bit-identical to recomputing the
    whole prompt."""
    base = prompt(110, 11)               # partial page: 11 % 4 != 0
    tails, mns = ([], [2, 9], [7, 3]), (12, 8, 8)
    st = {}
    plain = shared_run(model, base, tails, mns, share=False)
    shared = shared_run(model, base, tails, mns, share=True,
                        stats_out=st)
    assert shared == plain
    assert st["prefix_hits"] == 2
    assert st["prefix_tokens"] > 0
    assert st["kv_shared_peak"] >= 1


def test_spec_and_share_compose_bitexact(model):
    base = prompt(120, 10)
    tails, mns = ([], [6, 2], [1, 8]), (10, 10, 6)
    plain = shared_run(model, base, tails, mns, share=False,
                       ladder=(1, 4))
    both = shared_run(model, base, tails, mns, share=True, spec_k=4,
                      ladder=(1, 4))
    assert both == plain


def test_cow_concurrent_divergence_same_page(model):
    """The donor keeps decoding INTO the page a joiner just mapped
    (and the joiner prefills its divergent tail into it): both must
    COW privately and neither stream may corrupt the other."""
    base = prompt(130, 10)               # page 2 partial (10 % 4 = 2)
    st = {}
    # joiner extends the donor's FULL prompt -> shares the partial page
    plain = shared_run(model, base, ([], [9, 9, 1]), (14, 10),
                       share=False, warm_iters=6, ladder=(1, 2))
    shared = shared_run(model, base, ([], [9, 9, 1]), (14, 10),
                        share=True, warm_iters=6, ladder=(1, 2),
                        stats_out=st)
    assert shared == plain
    assert st["prefix_hits"] == 1
    assert st["kv"]["cow_copies"] >= 1, \
        "divergence inside a shared page must copy-on-write"


def test_refcount_exact_frees_on_shed_and_eos(model):
    """A mid-run shed (deadline) releases exactly the shed request's
    private tail: the donor keeps its pages, finishes bit-exact, and
    the pool drains to zero afterwards."""
    base = prompt(140, 9)
    plain = shared_run(model, base, ([],), (16,), share=False,
                       ladder=(1, 2))
    clk = FakeClock()
    eng = make_engine(model, ladder=(1, 2), prefix_share=True,
                      clock=clk)
    try:
        s1 = eng.submit(base, max_new=16)
        for _ in range(4):
            eng.step_once()
            eng.sync()
        # the joiner's deadline expires mid-decode: it sheds while
        # still holding shared prefix pages
        s2 = eng.submit(with_tail(base, [2, 2]), max_new=16,
                        deadline_ms=100.0)
        for _ in range(3):
            eng.step_once()
            eng.sync()
        clk.advance(10.0)                # way past the joiner deadline
        drive(eng)
        assert eng.stats["prefix_hits"] == 1
        assert eng.stats["deadline_missed"] == 1
        with pytest.raises(Exception):
            s2.result(0)
        assert s1.result(0) == plain[0], \
            "shedding a prefix-sharing neighbour corrupted the donor"
        assert eng.kv.used_pages() == 0
        assert eng.kv.shared_pages() == 0
        assert eng.kv.free_pages() == eng.kv.num_pages - 1
        assert not eng.kv._refcnt, "refcounts must drain to empty"
    finally:
        eng.close()


def test_shared_census_approaches_one_over_n(model):
    """N requests over one long shared prefix hold ~1/N the physical
    pages of N private copies: census-pinned page counts."""
    ps = 4
    base = prompt(150, 24)               # 6 full pages of shared prefix
    n = 4
    eng = make_engine(model, ladder=(1, 2, 4, 8), page_size=ps,
                      prefix_share=True, num_pages=160,
                      max_context=64)
    try:
        streams = [eng.submit(base, max_new=12)]
        for _ in range(8):
            eng.step_once()
            eng.sync()
        streams += [eng.submit(with_tail(base, [i, 2]),
                               max_new=12) for i in range(1, n)]
        # run until every request is seated and mid-decode
        for _ in range(6):
            eng.step_once()
            eng.sync()
        kv = eng.kv.stats()
        assert eng.stats["prefix_hits"] == n - 1
        # the 6 full base pages exist ONCE physically but n times
        # logically: logical - physical == (n-1) * 6
        assert kv["logical_pages"] - kv["used_pages"] == (n - 1) * 6
        assert kv["shared_pages"] == 6
        drive(eng)
        outs = [s.result(0) for s in streams]
        assert all(len(o) == 12 for o in outs)
        assert eng.kv.used_pages() == 0
    finally:
        eng.close()


def test_allocator_bytes_equal_census_bytes_with_sharing(model):
    """COW rebinds the page arrays' _data mid-run; the census handles
    must survive and the one-accounting-path equality must hold while
    shares and copies are live."""
    base = prompt(160, 10)
    eng = make_engine(model, ladder=(1, 2), prefix_share=True)
    try:
        census = telemetry.memory.census()
        s1 = eng.submit(base, max_new=12)
        for _ in range(5):
            eng.step_once()
            eng.sync()
        s2 = eng.submit(onp.concatenate([base, [1, 4]]), max_new=8)
        for _ in range(6):
            eng.step_once()
            eng.sync()
        pool = census.live_bytes_by_pool().get("kvcache", 0)
        assert pool >= eng.kv.total_bytes() > 0
        drive(eng)
        s1.result(0), s2.result(0)
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# the guarded zero-sync spec+shared run
# ---------------------------------------------------------------------------

def test_spec_share_run_zero_unblessed_syncs(model, monkeypatch):
    """12+ scheduler iterations of draft->verify + prefix sharing under
    MXNET_TRANSFER_GUARD=raise: COW copies and acceptance rollback are
    device-side; the retire stays the ONE blessed sync."""
    monkeypatch.setenv("MXNET_TRANSFER_GUARD", "raise")
    base = prompt(170, 9)
    eng = make_engine(model, ladder=(1, 4), spec_k=4,
                      prefix_share=True)
    try:
        eng.warmup()
        before = telemetry.value(telemetry.names.HOST_SYNCS,
                                 "wait_to_read") or 0
        streams = [eng.submit(base, max_new=14)]
        for _ in range(4):
            eng.step_once()
            eng.sync()
        streams += [eng.submit(with_tail(base, [i, 7]),
                               max_new=10) for i in range(2)]
        iters = drive(eng)
        after = telemetry.value(telemetry.names.HOST_SYNCS,
                                "wait_to_read") or 0
        assert iters + 4 >= 12
        assert [len(s.result(0)) for s in streams] == [14, 10, 10]
        assert after - before == 0, \
            "spec+share hot loop performed an unblessed host sync"
        assert eng.stats["spec_steps"] > 0
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# GQA transformer decode stack
# ---------------------------------------------------------------------------

def test_gqa_rejects_bad_geometry():
    from mxnet_tpu.gluon import GQADecoder
    with pytest.raises(MXNetError, match="multiple"):
        GQADecoder(d_model=32, num_heads=4, num_kv_heads=3)
    with pytest.raises(MXNetError, match="divisible"):
        GQADecoder(d_model=30, num_heads=4, num_kv_heads=2)


def test_gqa_engine_sizes_cache_by_kv_heads(gqa_model):
    eng = make_engine(gqa_model)
    try:
        assert eng.kv.num_heads == gqa_model.num_kv_heads == 2
        assert eng.kv.num_layers == gqa_model.num_layers == 2
        # dummy carries: (slots, 1) pass-throughs
        assert eng._h.shape == (eng.slots, 1)
    finally:
        eng.close()


def test_gqa_attention_matches_repeated_kv_reference():
    """paged_decode_attention with fewer K/V heads must equal the MHA
    result over explicitly repeated K/V heads."""
    from mxnet_tpu.ops.attention import paged_decode_attention
    rng = onp.random.RandomState(0)
    S, Hq, Hkv, D, P, ps = 3, 4, 2, 8, 6, 4
    q = jnp.asarray(rng.normal(size=(S, Hq, D)).astype("float32"))
    kp = jnp.asarray(rng.normal(size=(P, ps, Hkv, D)).astype("float32"))
    vp = jnp.asarray(rng.normal(size=(P, ps, Hkv, D)).astype("float32"))
    table = jnp.asarray(
        onp.array([[1, 2, 0], [3, 4, 0], [5, 1, 0]], onp.int32))
    lengths = jnp.asarray([7, 5, 2], jnp.int32)
    out = paged_decode_attention(q, kp, vp, table, lengths)
    rep = jnp.repeat(kp, Hq // Hkv, axis=2), \
        jnp.repeat(vp, Hq // Hkv, axis=2)
    ref = paged_decode_attention(q, rep[0], rep[1], table, lengths)
    assert out.shape == (S, Hq, D)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=1e-6, atol=1e-6)


def test_gqa_attention_rejects_nondivisible_heads():
    from mxnet_tpu.ops.attention import paged_decode_attention
    q = jnp.zeros((2, 4, 8), "float32")
    kp = jnp.zeros((4, 4, 3, 8), "float32")
    with pytest.raises(MXNetError, match="multiple|divis"):
        paged_decode_attention(q, kp, kp, jnp.zeros((2, 2), jnp.int32),
                               jnp.ones((2,), jnp.int32))


def test_gqa_engine_bitexact_spec_and_share(gqa_model):
    """One greedy batch run is the baseline for BOTH the speculative
    and the prefix-sharing transformer runs — neither may change a
    token."""
    base = prompt(210, 10)
    prompts = [prompt(200, 3), prompt(201, 4),
               base, with_tail(base, [3, 4])]
    mns = [8, 6, 8, 8]
    greedy = decode_all(gqa_model, prompts, mns, ladder=(1, 2))
    eng = make_engine(gqa_model, ladder=(1, 2), spec_k=3,
                      prefix_share=True)
    try:
        streams = [eng.submit(base, max_new=8)]
        for _ in range(4):               # register the donor prefix
            eng.step_once()
            eng.sync()
        streams += [eng.submit(p, max_new=m)
                    for p, m in zip(prompts[:2], mns[:2])]
        streams.append(eng.submit(prompts[3], max_new=8))
        drive(eng)
        got = [s.result(0) for s in streams]
    finally:
        eng.close()
    assert got == [greedy[2], greedy[0], greedy[1], greedy[3]]


def test_gqa_isolated_stream_matches_batched(gqa_model):
    """The continuous-batching invariant carries over to the
    transformer: a request decoded next to batch-mates emits the same
    tokens it emits alone."""
    p = prompt(220, 5)
    alone = decode_all(gqa_model, [p], [9], ladder=(1,))
    crowd = decode_all(gqa_model, [p, prompt(221, 3)], [9, 5],
                       ladder=(1, 2))
    assert crowd[0] == alone[0]
