"""Continuous-batching autoregressive decode engine (docs/SERVING.md).

Request-level batching (DynamicBatcher) is the wrong granularity for
autoregressive decode: requests retire after wildly different numbers
of steps, and a whole-batch scheduler holds every finished slot hostage
to the longest member (the Orca observation — iteration-level
scheduling, arXiv via vLLM/Orca lineage). This module schedules at the
STEP boundary instead:

- **Iteration-level scheduling.** Requests join and leave the running
  batch BETWEEN decode steps. The compiled step is shape-stable over a
  fixed ladder of slot-count buckets (``decode.slot_ladder`` /
  ``MXNET_DECODE_SLOTS``; AOT-compiled, warm-started from
  persistent compile cache) with a per-slot active mask; a slot freed by
  EOS/max-tokens is refilled from the queue on the next iteration.
- **Paged KV cache.** K/V history lives in :class:`~mxnet_tpu.serving
  .kvcache.PagedKVCache` pages behind a (slots, max_pages) page-table
  indirection, so admission control is simply "are there free pages" —
  a request that cannot reserve its worst-case pages is shed with a
  typed ``Overloaded(reason="kvcache")`` (composing the PR 15 EWMA/
  deadline shedder, which still applies first). Deadlines are
  re-projected PER TOKEN at retire: when the inter-token (TPOT) EWMA
  says the remaining tokens cannot land inside the request's
  deadline, the stream is shed mid-flight with a typed
  ``DeadlineExceeded`` and its KV pages free immediately for streams
  that can still make their budget.
- **Chunked prefill.** Long prompts are consumed ``decode.prefill_chunk``
  tokens at a time, strictly alternating with decode iterations when
  both kinds of work exist — a long prompt can never starve the
  running batch, and a short request's TTFT never waits on a long
  prompt ahead of it.
- **Single-step decode kernel.** The per-token recurrence runs through
  :func:`~mxnet_tpu.ops.kernels.rnn_scan.rnn_decode_step` (the
  block_t=1 rnn_scan variant behind the shared ``MXNET_PALLAS`` gate)
  and attention reads K/V through the page table via
  :func:`~mxnet_tpu.ops.attention.paged_decode_attention`.

Pipelining discipline: every step is dispatched async and pushed into a
:class:`~mxnet_tpu.engine.DispatchWindow`; the retire of a step is the
ONE blessed host sync, and that is where its tokens are read back and
streamed to the per-request :class:`DecodeStream` futures. Next-step
inputs chain DEVICE-side (the sampled-token array feeds the next
iteration without a host round trip), so the hot loop stays clean under
``MXNET_TRANSFER_GUARD=raise`` — a tier-1 test pins zero unblessed
syncs over a streamed multi-request run.

Slot-reuse safety: an in-flight step dispatched before a retire
discovered EOS writes one garbage token into the finished request's
(now freed) pages. That is safe by stream order — the device executes
steps in dispatch order, so the garbage write always lands BEFORE the
next occupant's prefill overwrites those pages — and it is budgeted:
admission reserves ``pages_needed(prompt + max_new + inflight)``.

**Speculative decode** (``decode.spec_k`` / ``MXNET_DECODE_SPEC_K``;
0 = off): a cheap host-side drafter (:class:`NgramDrafter` by default —
prompt-lookup over the request's own token history; any object with
``propose(history, k)`` plugs in, e.g. :class:`ModelDrafter` wrapping a
small engine-protocol model) proposes up to K tokens per slot, and ONE
``verify`` program — the chunked-prefill scan shape over the SAME
per-token cell (:func:`~mxnet_tpu.ops.kernels.rnn_scan
.rnn_verify_scan`) — scores all K positions, accepts the longest
prefix matching the model's own greedy continuation DEVICE-side, rolls
the recurrent carry back to the accepted position, and emits between 1
and K tokens per dispatch. Rejected positions wrote K/V beyond the
committed length; the rollback is pure length bookkeeping — attention
masks by ``lengths`` and the next step overwrites them. Emitted
streams are BIT-exact vs plain greedy decode (tier-1 pins it); the
verify bucket ladder AOT-compiles at :meth:`DecodeEngine.warmup`.

**Prefix sharing** (``decode.prefix_share`` /
``MXNET_DECODE_PREFIX_SHARE``): retired prefill chunks register their
committed pages in the cache's content-hash registry; a later request
whose prompt extends a registered prefix maps those physical pages
(refcounted), installs the registered recurrent-state snapshot, and
prefills only its unshared tail — admission prices only that tail.
First divergent write onto a page held by >= 2 requests triggers a
copy-on-write page copy (kvcache.py has the lifecycle).
"""
from __future__ import annotations

import logging
import os
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as onp

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from ..analysis import guard as _tguard
from ..engine import DispatchWindow
from ..ops.attention import paged_decode_attention
from ..ops.kernels import pallas_mode
from ..ops.kernels.rnn_scan import rnn_decode_step, rnn_verify_scan
from .kvcache import KV_PAGE_SIZE, PagedKVCache, pages_needed
from .resilience import (DeadlineExceeded, Overloaded, ServingShutdown,
                         default_deadline_ms, shed_mode)
from .batcher import queue_depth

__all__ = ["DecodeEngine", "DecodeStream", "TinyDecoder", "run_decode",
           "NgramDrafter", "ModelDrafter",
           "slot_ladder", "kv_page_size", "prefill_chunk", "spec_k",
           "prefix_share", "DECODE_SLOT_LADDER", "PREFILL_CHUNK",
           "SPEC_K", "PREFIX_SHARE"]

_LOG = logging.getLogger("mxnet_tpu.serving.decode")

#: shipped slot-count ladder (``decode.slot_ladder`` / ``MXNET_DECODE_SLOTS``)
DECODE_SLOT_LADDER = (1, 2, 4, 8)
#: shipped prompt-chunk width (``decode.prefill_chunk`` /
#: ``MXNET_DECODE_PREFILL_CHUNK``)
PREFILL_CHUNK = 16
#: shipped max draft tokens per speculative step (``decode.spec_k`` /
#: ``MXNET_DECODE_SPEC_K``; 0 = speculative decode off)
SPEC_K = 0
#: shipped prefix-cache sharing switch (``decode.prefix_share`` /
#: ``MXNET_DECODE_PREFIX_SHARE``; 1 = on)
PREFIX_SHARE = 1


def _parse_ladder(v) -> Tuple[int, ...]:
    """'1,2,4,8' (or an int sequence) -> sorted unique positive tuple."""
    if isinstance(v, (tuple, list)):
        vals = tuple(sorted({int(x) for x in v}))
    else:
        vals = tuple(sorted({int(x) for x in
                             str(v).replace(" ", "").split(",") if x}))
    if not vals or vals[0] < 1:
        raise ValueError(f"bad slot ladder {v!r}")
    return vals


def slot_ladder() -> Tuple[int, ...]:
    """THE slot-ladder accessor: ``MXNET_DECODE_SLOTS`` ('1,2,4,8')
    when set and a valid ladder, else the default."""
    try:
        return _parse_ladder(os.environ["MXNET_DECODE_SLOTS"])
    except (KeyError, ValueError):
        return DECODE_SLOT_LADDER


def _env_int(name: str, default: int, lo: int, hi: int) -> int:
    """``int(os.environ[name])`` clamped to [lo, hi]; ``default`` when
    unset or unparseable."""
    try:
        return max(lo, min(int(os.environ[name]), hi))
    except (KeyError, ValueError):
        return default


def kv_page_size() -> int:
    """Tokens per KV page: ``MXNET_DECODE_KV_PAGE_SIZE`` (1..4096),
    else ``kvcache.KV_PAGE_SIZE``."""
    return _env_int("MXNET_DECODE_KV_PAGE_SIZE", KV_PAGE_SIZE, 1, 4096)


def prefill_chunk() -> int:
    """Prompt tokens one prefill iteration consumes:
    ``MXNET_DECODE_PREFILL_CHUNK`` (1..4096), else the default.
    Smaller = better decode-batch latency, larger = better prefill
    throughput."""
    return _env_int("MXNET_DECODE_PREFILL_CHUNK", PREFILL_CHUNK, 1, 4096)


def spec_k() -> int:
    """Max draft tokens per speculative-decode step (0 disables):
    ``MXNET_DECODE_SPEC_K`` (0..64), else the default."""
    return _env_int("MXNET_DECODE_SPEC_K", SPEC_K, 0, 64)


def prefix_share() -> bool:
    """Whether the engine shares prefix-cache pages across requests:
    ``MXNET_DECODE_PREFIX_SHARE`` (0 = off, any other integer on),
    else the default."""
    try:
        return int(os.environ["MXNET_DECODE_PREFIX_SHARE"]) != 0
    except (KeyError, ValueError):
        return bool(PREFIX_SHARE)


def _telemetry():
    from .. import telemetry
    return telemetry


# ---------------------------------------------------------------------------
# speculative drafters
# ---------------------------------------------------------------------------

class NgramDrafter:
    """The default drafter: prompt-lookup / n-gram matching over the
    request's OWN token history (prompt + everything emitted so far).
    ``propose`` finds the most recent earlier occurrence of the last
    ``n`` tokens and returns (up to ``k``) of the tokens that followed
    it — free to compute, host-side, and exact on repetitive suffixes
    (code, templates, greedy loops). Proposals are only ever drafts:
    the verify program accepts at most the model's own greedy
    continuation, so a bad draft costs speed, never correctness.
    """

    def __init__(self, n: int = 2, min_n: int = 1):
        self.n = max(1, int(n))
        self.min_n = max(1, min(int(min_n), self.n))

    def propose(self, history, k: int) -> List[int]:
        k = int(k)
        if k <= 0 or len(history) < 2:
            return []
        hist = list(history)
        L = len(hist)
        for n in range(min(self.n, L - 1), self.min_n - 1, -1):
            tail = hist[L - n:]
            # most recent earlier occurrence of the suffix n-gram
            for i in range(L - n - 1, -1, -1):
                if hist[i:i + n] == tail:
                    cont = hist[i + n:i + n + k]
                    if cont:
                        return [int(t) for t in cont]
                    break
        return []


class ModelDrafter:
    """Pluggable small-model drafter: greedy-decodes ``k`` draft tokens
    with a SECOND engine-protocol model (same ``decode_step`` contract,
    its own tiny state per request) — the classic two-model speculative
    setup. Draft quality tracks how well the small model imitates the
    target; correctness never depends on it. Host-side readback of each
    draft token makes this drafter sync per proposal, so it is NOT for
    transfer-guard-pinned paths — the default :class:`NgramDrafter`
    is."""

    def __init__(self, model):
        self.model = model
        self._state: Dict[int, tuple] = {}

    def reset(self, key: int):
        self._state.pop(key, None)

    def propose(self, history, k: int, key: int = 0) -> List[int]:
        k = int(k)
        if k <= 0 or not len(history):
            return []
        import jax.numpy as _jnp
        h, c = self.model.init_state(1)
        # replay the history through the cell (small model, tiny state);
        # incremental caching per key keeps this O(new tokens)
        cached = self._state.get(key)
        start = 0
        if cached is not None and cached[0] <= len(history) \
                and list(history[:cached[0]]) == cached[1]:
            start, _, h, c = cached[0], cached[1], cached[2], cached[3]
        for t in history[start:]:
            tok = _jnp.asarray([int(t)], _jnp.int32)
            h, c = self.model._cell(self.model.params, tok, h, c)
        self._state[key] = (len(history), list(history), h, c)
        out: List[int] = []
        logits_of = getattr(self.model, "draft_logits", None)
        cur = int(history[-1])
        for _ in range(k):
            if logits_of is None:
                break
            cur = int(logits_of(self.model.params, h).argmax())
            out.append(cur)
            tok = _jnp.asarray([cur], _jnp.int32)
            h, c = self.model._cell(self.model.params, tok, h, c)
        return out


def _accept_longest_prefix(ys, hs, cs, tokens, n_draft, active):
    """Device-side acceptance for one verify dispatch.

    ``ys`` (S, K): the model's greedy token at each verified position;
    ``hs``/``cs`` (K, S, ...): masked per-position state trajectories;
    ``tokens`` (S, K): the fed inputs (position 0 = last committed
    token, 1.. = drafts); ``n_draft`` (S,): valid input count.

    Position t's output is emitted iff every draft before it matched
    the model's own continuation (``ys[t-1] == tokens[t]`` for all
    t' <= t), so the emitted block is EXACTLY what sequential greedy
    decode would have produced — acceptance can shorten a step, never
    change a token. Returns (emitted (S, K), n_acc (S,), next_tok (S,),
    h_fin, c_fin) with the state rolled back to the last accepted
    position (inactive slots bit-preserve everything).
    """
    S, K = ys.shape
    if K > 1:
        idx = jnp.arange(1, K)[None, :]
        eq = (ys[:, :-1] == tokens[:, 1:]) & (idx < n_draft[:, None])
        n_acc = 1 + jnp.cumprod(eq.astype(jnp.int32), axis=1).sum(axis=1)
    else:
        n_acc = jnp.ones((S,), jnp.int32)
    n_acc = jnp.minimum(n_acc, jnp.maximum(n_draft, 1)).astype(jnp.int32)
    a_idx = jnp.maximum(n_acc - 1, 0)

    def _at_accept(traj):
        if traj is None:
            return None
        t = jnp.moveaxis(traj, 0, 1)              # (S, K, ...)
        ix = a_idx.reshape((S,) + (1,) * (t.ndim - 1))
        return jnp.take_along_axis(t, ix, axis=1)[:, 0]

    h_fin = _at_accept(hs)
    c_fin = _at_accept(cs)
    next_tok = jnp.take_along_axis(ys, a_idx[:, None], axis=1)[:, 0]
    next_tok = jnp.where(active, next_tok, tokens[:, 0])
    n_acc = jnp.where(active, n_acc, 0)
    return ys, n_acc, next_tok, h_fin, c_fin


# ---------------------------------------------------------------------------
# reference model
# ---------------------------------------------------------------------------

class TinyDecoder:
    """The reference autoregressive decode model — one LSTM cell through
    :func:`rnn_decode_step` plus one attention layer reading K/V through
    the page table — small enough for CPU tier-1 yet exercising BOTH
    decode kernels and the full paged-cache read/write path.

    Any model driving :class:`DecodeEngine` implements this protocol:
    ``params`` (a pytree), ``num_layers``/``num_heads``/``head_dim``/
    ``d_model``, :meth:`init_state`, :meth:`decode_step` and
    :meth:`prefill_chunk` (both pure functions of their inputs — the
    engine jits and AOT-compiles them per slot bucket).
    """

    num_layers = 1

    def __init__(self, vocab: int = 64, d_model: int = 32,
                 num_heads: int = 2, seed: int = 0):
        if d_model % num_heads:
            raise MXNetError(f"d_model={d_model} not divisible by "
                             f"num_heads={num_heads}")
        self.vocab = int(vocab)
        self.d_model = int(d_model)
        self.num_heads = int(num_heads)
        self.head_dim = self.d_model // self.num_heads
        rng = onp.random.RandomState(seed)
        H = self.d_model

        def mat(*shape, scale=0.3):
            return jnp.asarray(
                rng.normal(0.0, scale, shape).astype("float32"))

        self.params = {
            "embed": mat(self.vocab, H, scale=0.5),
            "w_ih": mat(4 * H, H), "b_ih": jnp.zeros((4 * H,), "float32"),
            "w_hh": mat(4 * H, H), "b_hh": jnp.zeros((4 * H,), "float32"),
            "wq": mat(H, H), "wk": mat(H, H), "wv": mat(H, H),
            "wo": mat(H, H),
        }

    def init_state(self, slots: int):
        H = self.d_model
        return (jnp.zeros((slots, H), "float32"),
                jnp.zeros((slots, H), "float32"))

    # -- one fused sub-step shared by decode and prefill (parity by
    #    construction: a token is processed by the same math either way)
    def _cell(self, params, tokens, h, c):
        emb = params["embed"][tokens]
        xw = emb @ params["w_ih"].T + params["b_ih"]
        return rnn_decode_step(xw, h, c, params["w_hh"], params["b_hh"],
                               "lstm")

    def _qkv(self, params, h2):
        S = h2.shape[0]
        nH, hd = self.num_heads, self.head_dim
        q = (h2 @ params["wq"]).reshape(S, nH, hd)
        k = (h2 @ params["wk"]).reshape(S, nH, hd)
        v = (h2 @ params["wv"]).reshape(S, nH, hd)
        return q, k, v

    def _logits(self, params, h2, attn):
        out = h2 + attn.reshape(h2.shape) @ params["wo"]
        return out @ params["embed"].T

    def decode_step(self, params, tokens, h, c, k_pages, v_pages,
                    pidx, poff, table, lengths, active):
        """One iteration over every slot: consume ``tokens`` (each
        slot's last token), write this position's K/V through the page
        table, attend over the slot's history, emit the next greedy
        token. Inactive slots are bit-preserved (masked carry) and
        their writes land on the null page."""
        h2, c2 = self._cell(params, tokens, h, c)
        act = active[:, None]
        h_new = jnp.where(act, h2, h)
        c_new = jnp.where(act, c2, c)
        q, k, v = self._qkv(params, h2)
        pidx = jnp.where(active, pidx, 0)
        poff = jnp.where(active, poff, 0)
        k_pages = k_pages.at[0, pidx, poff].set(k.astype(k_pages.dtype))
        v_pages = v_pages.at[0, pidx, poff].set(v.astype(v_pages.dtype))
        attn = paged_decode_attention(q, k_pages[0], v_pages[0],
                                      table, lengths)
        nxt = jnp.argmax(self._logits(params, h2, attn),
                         axis=-1).astype(jnp.int32)
        nxt = jnp.where(active, nxt, tokens)
        return nxt, h_new, c_new, k_pages, v_pages

    def prefill_chunk(self, params, tokens, h, c, k_pages, v_pages,
                      start_len, n_valid, reset, active, table,
                      page_size: int):
        """Consume up to ``tokens.shape[1]`` prompt tokens for the
        active slot(s): scan the SAME per-token cell, writing each
        position's K/V through the page table; the returned token is
        the greedy continuation of the last valid position (meaningful
        on a prompt's final chunk — the request's first token)."""
        S, C = tokens.shape
        h = jnp.where(reset[:, None], 0.0, h)
        c = jnp.where(reset[:, None], 0.0, c)

        def body(carry, t):
            h, c, kp, vp = carry
            tok = tokens[:, t]
            valid = active & (t < n_valid)
            h2, c2 = self._cell(params, tok, h, c)
            vm = valid[:, None]
            h = jnp.where(vm, h2, h)
            c = jnp.where(vm, c2, c)
            _, k, v = self._qkv(params, h2)
            pos = start_len + t
            page = jnp.take_along_axis(
                table, (pos // page_size)[:, None], axis=1)[:, 0]
            pg = jnp.where(valid, page, 0)
            off = jnp.where(valid, pos % page_size, 0)
            kp = kp.at[0, pg, off].set(k.astype(kp.dtype))
            vp = vp.at[0, pg, off].set(v.astype(vp.dtype))
            return (h, c, kp, vp), None

        (h, c, k_pages, v_pages), _ = lax.scan(
            body, (h, c, k_pages, v_pages), jnp.arange(C))
        lengths = jnp.maximum(start_len + n_valid, 1)
        q, _, _ = self._qkv(params, h)
        attn = paged_decode_attention(q, k_pages[0], v_pages[0],
                                      table, lengths)
        nxt = jnp.argmax(self._logits(params, h, attn),
                         axis=-1).astype(jnp.int32)
        nxt = jnp.where(active, nxt, 0)
        return nxt, h, c, k_pages, v_pages

    def verify_chunk(self, params, tokens, h, c, k_pages, v_pages,
                     start_len, n_draft, active, table,
                     page_size: int):
        """Score ``tokens`` (S, K: last committed token + up to K-1
        drafts) in ONE dispatch: the recurrence runs the masked
        verify scan (the SAME per-position cell as :meth:`decode_step`
        — the carry never depends on attention), then each position
        writes its K/V through the page table and emits the greedy
        token over exactly the history sequential decode would see.
        Returns per-position tokens ``ys`` (S, K) plus the full state
        trajectories for device-side acceptance rollback."""
        S, K = tokens.shape
        emb = params["embed"][tokens]                     # (S, K, H)
        xw = (emb @ params["w_ih"].T
              + params["b_ih"]).transpose(1, 0, 2)        # (K, S, 4H)
        valid = active[None, :] & (jnp.arange(K)[:, None]
                                   < n_draft[None, :])
        hs, cs = rnn_verify_scan(xw, h, c, params["w_hh"],
                                 params["b_hh"], "lstm", valid)

        def body(kv, t):
            kp, vp = kv
            h2 = hs[t]
            q, k, v = self._qkv(params, h2)
            val = valid[t]
            pos = start_len + t
            page = jnp.take_along_axis(
                table, (pos // page_size)[:, None], axis=1)[:, 0]
            pg = jnp.where(val, page, 0)
            off = jnp.where(val, pos % page_size, 0)
            kp = kp.at[0, pg, off].set(k.astype(kp.dtype))
            vp = vp.at[0, pg, off].set(v.astype(vp.dtype))
            lengths = jnp.where(val, pos + 1, 1)
            attn = paged_decode_attention(q, kp[0], vp[0], table,
                                          lengths)
            y = jnp.argmax(self._logits(params, h2, attn),
                           axis=-1).astype(jnp.int32)
            return (kp, vp), y

        (k_pages, v_pages), ys = lax.scan(
            body, (k_pages, v_pages), jnp.arange(K))
        return ys.T, hs, cs, k_pages, v_pages


# ---------------------------------------------------------------------------
# streaming future
# ---------------------------------------------------------------------------

class DecodeStream:
    """Per-request streaming future: each generated token is delivered
    as the step that computed it retires through the dispatch window.
    Iterate for tokens as they arrive, or :meth:`result` for the full
    sequence; :meth:`record` yields the streaming-latency record
    (``ttft_s`` / ``tpot_s`` / ``tokens``) loadgen aggregates."""

    def __init__(self, t_submit: float):
        # bare on purpose: decode hot loop: per-token budget; leaf, never nests
        self._cv = threading.Condition()  # mx-lint: allow=MXA009
        self._tokens: List[int] = []
        self._times: List[float] = []
        self._cursor = 0
        self._done = False
        self._exc: Optional[BaseException] = None
        self.t_submit = t_submit
        # speculative-decode accounting (empty unless the engine runs
        # a draft->verify loop): per-step emitted-token counts plus
        # drafted/accepted totals — loadgen.streaming_summary turns
        # these into acceptance_rate and tokens_per_step percentiles
        self._step_tokens: List[int] = []
        self._drafted = 0
        self._accepted = 0

    # -- engine side (called under the engine lock)
    def _deliver(self, tok: int, t: float):
        with self._cv:
            self._tokens.append(int(tok))
            self._times.append(float(t))
            self._cv.notify_all()

    def _record_step(self, emitted: int, drafted: int, accepted: int):
        with self._cv:
            self._step_tokens.append(int(emitted))
            self._drafted += int(drafted)
            self._accepted += int(accepted)

    def _finish(self):
        with self._cv:
            self._done = True
            self._cv.notify_all()

    def _fail(self, exc: BaseException):
        with self._cv:
            self._exc = exc
            self._done = True
            self._cv.notify_all()

    # -- client side
    def next_token(self, timeout: Optional[float] = None) -> Optional[int]:
        """Next token, blocking until one arrives; None at end of
        stream. Raises the request's typed failure (after any tokens
        delivered before it) once the cursor reaches it."""
        with self._cv:
            if not self._cv.wait_for(
                    lambda: self._cursor < len(self._tokens) or self._done,
                    timeout=timeout):
                raise MXNetError("DecodeStream.next_token timed out")
            if self._cursor < len(self._tokens):
                tok = self._tokens[self._cursor]
                self._cursor += 1
                return tok
            if self._exc is not None:
                raise self._exc
            return None

    def __iter__(self):
        while True:
            tok = self.next_token()
            if tok is None:
                return
            yield tok

    def result(self, timeout: Optional[float] = None) -> List[int]:
        with self._cv:
            if not self._cv.wait_for(lambda: self._done, timeout=timeout):
                raise MXNetError("DecodeStream.result timed out")
            if self._exc is not None:
                raise self._exc
            return list(self._tokens)

    @property
    def done(self) -> bool:
        with self._cv:
            return self._done

    @property
    def ttft_s(self) -> Optional[float]:
        with self._cv:
            return (self._times[0] - self.t_submit) if self._times else None

    def record(self) -> dict:
        """Streaming-latency record: the shape
        ``loadgen.streaming_summary`` aggregates."""
        with self._cv:
            times = list(self._times)
            n = len(times)
            rec = {
                "tokens": n,
                "ttft_s": (times[0] - self.t_submit) if n else None,
                "tpot_s": [times[i] - times[i - 1] for i in range(1, n)],
                "wall_s": (times[-1] - self.t_submit) if n else None,
                "outcome": ("error" if self._exc is not None
                            else "ok" if self._done else "pending"),
            }
            if self._step_tokens:
                rec["step_tokens"] = list(self._step_tokens)
                rec["spec_drafted"] = self._drafted
                rec["spec_accepted"] = self._accepted
            return rec


class _Request:
    __slots__ = ("prompt", "max_new", "eos", "stream", "deadline",
                 "t_submit", "t_last_tok", "slot", "phase", "pos",
                 "generated", "done", "npages", "seq", "need_tokens",
                 "history", "inflight", "shared_len")

    def __init__(self, prompt, max_new, eos, stream, deadline, npages,
                 seq, need_tokens=0):
        self.prompt = prompt
        self.max_new = max_new
        self.eos = eos
        self.stream = stream
        self.deadline = deadline
        self.t_submit = stream.t_submit
        self.t_last_tok = stream.t_submit
        self.slot = -1
        self.phase = "queued"      # queued -> prefill -> decode
        self.pos = 0               # prompt tokens consumed
        self.generated = 0
        self.done = False
        self.npages = npages
        self.seq = seq
        self.need_tokens = need_tokens   # worst-case KV positions
        # host-side token history (prompt + emitted): what the drafter
        # proposes from and what prefix registration keys on
        self.history = [int(t) for t in prompt]
        self.inflight = False      # a verify step is in flight
        self.shared_len = 0        # prompt tokens seated from the cache


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class DecodeEngine:
    """Iteration-level scheduler over a fixed slot ladder with a paged
    KV cache (module docstring has the design).

    ``static=True`` flips ONLY the scheduling policy to the classic
    whole-batch baseline — fill every slot, prefill all prompts, decode
    until the LAST member finishes, then admit the next batch — with
    the identical compiled programs, which is what makes the bench
    ``decode`` leg an honest continuous-vs-static A/B.

    Deterministic tests drive a ``start=False`` engine manually with
    :meth:`step_once` (+ :meth:`sync` to retire in-flight steps) and an
    injected ``clock``.
    """

    def __init__(self, model, *, ladder: Optional[Sequence[int]] = None,
                 num_pages: Optional[int] = None,
                 page_size: Optional[int] = None,
                 max_context: int = 128, max_new_default: int = 16,
                 eos_id: Optional[int] = None,
                 depth: Optional[int] = None, inflight: int = 1,
                 static: bool = False, admission: bool = True,
                 dtype: str = "float32",
                 clock: Callable[[], float] = time.perf_counter,
                 start: bool = True,
                 spec_k: Optional[int] = None, drafter=None,
                 prefix_share: Optional[bool] = None):
        self.model = model
        self._ladder = _parse_ladder(ladder if ladder is not None
                                     else slot_ladder())
        self.slots = self._ladder[-1]
        ps = int(page_size) if page_size else kv_page_size()
        self._chunk = prefill_chunk()
        self._spec_k = (globals()["spec_k"]() if spec_k is None
                        else max(0, int(spec_k)))
        self._prefix_share = (globals()["prefix_share"]()
                              if prefix_share is None
                              else bool(prefix_share))
        self._drafter = drafter if drafter is not None else \
            (NgramDrafter() if self._spec_k else None)
        self.max_context = int(max_context)
        self.max_pages_per_slot = pages_needed(self.max_context, ps)
        if num_pages is None:
            num_pages = 1 + self.slots * self.max_pages_per_slot
        # GQA models cache fewer K/V heads than they query with
        kv_heads = int(getattr(model, "num_kv_heads", model.num_heads))
        self.kv = PagedKVCache(model.num_layers, kv_heads,
                               model.head_dim, num_pages, ps, dtype=dtype)
        self._h, self._c = model.init_state(self.slots)
        self._tokens_dev = jnp.zeros((self.slots,), jnp.int32)
        self._table = onp.zeros((self.slots, self.max_pages_per_slot),
                                onp.int32)
        self._device_len = onp.zeros(self.slots, onp.int64)
        self._occupant: List[Optional[_Request]] = [None] * self.slots
        self._queue: "deque[_Request]" = deque()
        self._depth = queue_depth() if depth is None else max(1, int(depth))
        self.max_new_default = max(1, int(max_new_default))
        self.eos_id = eos_id
        self.static = bool(static)
        self.admission = bool(admission)
        # bare on purpose: decode hot loop: per-token budget; leaf, never nests
        self._lock = threading.RLock()  # mx-lint: allow=MXA009
        # bare on purpose: decode hot loop: per-token budget; leaf, never nests
        self._work = threading.Condition(self._lock)  # mx-lint: allow=MXA009
        self._clock = clock
        self._window = DispatchWindow(max_inflight=max(0, int(inflight)),
                                      what="decode step",
                                      sync_fn=self._retire_sync)
        self._programs: Dict[tuple, dict] = {}
        self._n_traces = 0
        self._seq = 0
        self._tag = 0
        self._draining = False
        self._dead: Optional[BaseException] = None
        self._ewma_step: Optional[float] = None
        # inter-token-gap EWMA (TPOT): the per-token deadline
        # re-projection sheds a stream mid-flight when the projected
        # remaining decode time cannot land inside its deadline
        self._ewma_tpot: Optional[float] = None
        self._last_was_prefill = False
        self.stats = {"submitted": 0, "completed": 0, "rejected": 0,
                      "deadline_missed": 0, "shed_midstream": 0,
                      "steps": 0, "prefill_chunks": 0, "tokens": 0,
                      "kv_util_peak": 0.0,
                      "spec_steps": 0, "spec_drafted": 0,
                      "spec_accepted": 0,
                      "accept_hist": {},     # accepted-block len -> n
                      "prefix_hits": 0, "prefix_tokens": 0,
                      "kv_shared_peak": 0}
        t = _telemetry()
        reg = t.registry()
        self._m_tokens = reg.counter(t.names.DECODE_TOKENS)
        self._m_active = reg.gauge(t.names.DECODE_ACTIVE_SLOTS)
        self._m_ttft = reg.histogram(t.names.DECODE_TTFT_SECONDS)
        self._m_tpot = reg.histogram(t.names.DECODE_TPOT_SECONDS)
        self._m_rejected = reg.counter(t.names.SERVING_REJECTED,
                                       label_key="reason")
        self._m_drafted = reg.counter(t.names.DECODE_SPEC_DRAFTED)
        self._m_accepted = reg.counter(t.names.DECODE_SPEC_ACCEPTED)
        self._m_aot_fallback = reg.counter(t.names.DECODE_AOT_FALLBACK)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        if start:
            self._thread = threading.Thread(
                target=self._serve_loop, name="mx-decode-engine",
                daemon=True)
            self._thread.start()

    # ---------------- compiled programs ----------------
    def _entry(self, kind: str, bucket: int) -> dict:
        key = (kind, bucket)
        entry = self._programs.get(key)
        if entry is None:
            entry = {"key": key, "fn": self._shared_program(kind),
                     "exe": None, "analysis": None}
            self._programs[key] = entry
        return entry

    def _shared_program(self, kind: str):
        """One ``jax.jit`` wrapper per (model, kind, page geometry,
        kernel gate), shared by every engine over the same model: a
        rebuilt engine (fleet restart, A/B run, test) reuses the
        already-traced program for any slot bucket it has seen, paying
        zero retrace. The wrapper is bucket-polymorphic (jit re-traces
        per leading-dim shape internally); only AOT ``exe`` artifacts
        stay per-engine."""
        model = self.model
        ps = self.kv.page_size
        cache = model.__dict__.setdefault("_mx_decode_programs", {})
        ck = (kind, ps, pallas_mode())
        cached = cache.get(ck)
        if cached is not None:
            cached["owner"]["eng"] = self
            return cached["fn"]
        owner = {"eng": self}

        def count_trace():
            eng = owner["eng"]
            if eng is not None:
                eng._n_traces += 1

        if kind == "decode":
            def raw(params, tokens, h, c, kp, vp, pidx, poff,
                    table, lengths, active):
                count_trace()
                return model.decode_step(params, tokens, h, c, kp,
                                         vp, pidx, poff, table,
                                         lengths, active)
        elif kind == "verify":
            def raw(params, tokens, h, c, kp, vp, start_len,
                    n_draft, active, table):
                count_trace()
                ys, hs, cs, kp, vp = model.verify_chunk(
                    params, tokens, h, c, kp, vp, start_len,
                    n_draft, active, table, page_size=ps)
                emitted, n_acc, nxt, h2, c2 = _accept_longest_prefix(
                    ys, hs, cs, tokens, n_draft, active)
                return emitted, n_acc, nxt, h2, c2, kp, vp
        else:
            def raw(params, tokens, h, c, kp, vp, start_len,
                    n_valid, reset, active, table):
                count_trace()
                return model.prefill_chunk(params, tokens, h, c,
                                           kp, vp, start_len,
                                           n_valid, reset, active,
                                           table, page_size=ps)
        cache[ck] = {"fn": jax.jit(raw, donate_argnums=(4, 5)),
                     "owner": owner}
        return cache[ck]["fn"]

    def _example_args(self, kind: str, bucket: int):
        """ShapeDtypeStruct mirrors of one bucket's runtime arguments —
        the lowering/AOT example (no device allocation)."""
        b = int(bucket)
        sds = jax.ShapeDtypeStruct
        params = jax.tree_util.tree_map(
            lambda a: sds(jnp.shape(a), a.dtype), self.model.params)
        kv = sds((self.kv.num_layers, self.kv.num_pages,
                  self.kv.page_size, self.kv.num_heads,
                  self.kv.head_dim), jnp.dtype(self.kv.dtype))
        i32 = jnp.dtype("int32")
        table = sds((b, self.max_pages_per_slot), i32)
        # state mirrors follow the LIVE state arrays (an attention-only
        # model carries dummy (slots, 1) pass-throughs, the RNN carries
        # (slots, d_model) — the program must match either)
        h = sds((b,) + tuple(self._h.shape[1:]), self._h.dtype)
        c = sds((b,) + tuple(self._c.shape[1:]), self._c.dtype)
        if kind == "decode":
            return (params, sds((b,), i32), h, c, kv, kv,
                    sds((b,), i32), sds((b,), i32), table,
                    sds((b,), i32), sds((b,), jnp.dtype(bool)))
        if kind == "verify":
            return (params, sds((b, self._spec_k + 1), i32), h, c,
                    kv, kv, sds((b,), i32), sds((b,), i32),
                    sds((b,), jnp.dtype(bool)), table)
        return (params, sds((b, self._chunk), i32), h, c, kv, kv,
                sds((b,), i32), sds((b,), i32),
                sds((b,), jnp.dtype(bool)),
                sds((b,), jnp.dtype(bool)), table)

    def warmup(self, buckets: Optional[Sequence[int]] = None) -> dict:
        """AOT-compile the decode + prefill program of every ladder
        bucket (``.lower().compile()``, warm-started from the
        persistent compile cache) so no request ever eats a
        first-iteration compile. Returns {(kind, bucket): executable}."""
        out = {}
        kinds = ("decode", "prefill", "verify") if self._spec_k > 0 \
            else ("decode", "prefill")
        for b in (buckets or self._ladder):
            for kind in kinds:
                entry = self._entry(kind, int(b))
                if entry["exe"] is None:
                    n_before = self._n_traces
                    try:
                        entry["exe"] = entry["fn"].lower(
                            *self._example_args(kind, int(b))).compile()
                    finally:
                        self._n_traces = n_before
                out[(kind, int(b))] = entry["exe"]
        return out

    def _call(self, entry: dict, args: tuple):
        fn = entry["exe"] if entry["exe"] is not None else entry["fn"]
        try:
            return fn(*args)
        except (TypeError, ValueError) as e:
            if entry["exe"] is None:
                raise
            # AOT signature drifted: drop the executable and re-jit.
            # Counted and logged because the next call compiles inside
            # a request; once per entry, since the executable is gone
            entry["exe"] = None
            self._m_aot_fallback.inc()
            _LOG.warning(
                "decode engine: AOT %s executable of slot bucket %d "
                "rejected its arguments (%s: %s); dropped, re-jitting "
                "inside this call", *entry["key"], type(e).__name__, e)
            return entry["fn"](*args)

    # ---------------- static analysis ----------------
    @property
    def mode(self) -> str:
        return "predict"

    @property
    def n_traces(self) -> int:
        return self._n_traces

    def lower_entry(self, *args, batch_size: Optional[int] = None,
                    **kwargs):
        """Lower one slot bucket's DECODE program for static analysis —
        the same artifact contract as ``CompiledPredictor.lower_entry``
        so the program lint runs unchanged over the decode engine."""
        bucket = self._bucket_for(int(batch_size) if batch_size
                                  else self.slots)
        entry = self._entry("decode", bucket)
        if entry["analysis"] is not None:
            return entry["analysis"]
        example = self._example_args("decode", bucket)
        n_before = self._n_traces
        try:
            lowered = entry["fn"].lower(*example)
            try:
                jaxpr = jax.make_jaxpr(entry["fn"])(*example)
            except Exception:       # pragma: no cover - defensive
                jaxpr = None
        finally:
            self._n_traces = n_before
        info = dict(kind="predict", mode="predict", lowered=lowered,
                    jaxpr=jaxpr, mesh=None, axis=None,
                    expected_donated=None, unit_sizes=[],
                    n_params=len(jax.tree_util.tree_leaves(
                        self.model.params)),
                    n_state_leaves=0, blessed_dtypes=[], report=None)
        entry["analysis"] = info
        return info

    def analyze(self, batch_size: Optional[int] = None):
        """Full program lint of the decode-step program
        (:class:`~mxnet_tpu.analysis.ProgramReport`, ``predict``
        expectations: no collectives, no unblessed host transfers, no
        stranded fusables)."""
        from ..analysis.program import analyze_step
        return analyze_step(self, batch_size=batch_size)

    # ---------------- admission ----------------
    def _reject(self, reason: str, msg: str):
        self.stats["rejected"] += 1
        self._m_rejected.inc(label=reason)
        raise Overloaded(msg, reason=reason)

    def submit(self, prompt, max_new: Optional[int] = None,
               eos: Optional[int] = None,
               deadline_ms: Optional[float] = None) -> DecodeStream:
        """Admit one request (or shed it with a typed ``Overloaded``)
        and return its token stream. Admission control, in order:
        draining, queue depth, the PR 15 EWMA deadline shedder, and KV
        page reservation (``reason="kvcache"``) — a request that cannot
        get its worst-case pages up front is shed NOW rather than
        corrupting a neighbour mid-flight."""
        prompt = onp.asarray(prompt, onp.int32).ravel()
        if prompt.size < 1:
            raise MXNetError("decode prompt must have >= 1 token")
        mn = self.max_new_default if max_new is None else max(1,
                                                              int(max_new))
        if deadline_ms is None:
            deadline_ms = default_deadline_ms()
        with self._lock:
            if self._dead is not None:
                raise ServingShutdown(
                    "DecodeEngine is shut down") from self._dead
            if self._draining:
                self._reject("draining",
                             "DecodeEngine is draining; request shed")
            if len(self._queue) >= self._depth:
                self._reject("queue",
                             f"decode queue full ({self._depth})")
            slack = max(1, self._window.max_inflight)
            if self._spec_k:
                # a verify step writes up to spec_k draft positions
                # past the committed length before acceptance trims
                slack += self._spec_k
            need_tokens = int(prompt.size) + mn + slack
            if need_tokens > self.max_pages_per_slot * self.kv.page_size:
                raise MXNetError(
                    f"request needs {need_tokens} KV positions "
                    f"(prompt {prompt.size} + max_new {mn} + inflight "
                    f"slack {slack}) > max_context {self.max_context}")
            npages = pages_needed(need_tokens, self.kv.page_size)
            if self._prefix_share:
                # price only the unshared tail: FULL pages covered by a
                # registered prefix are mapped, not allocated (the seat
                # re-checks and falls back to worst case if the entry
                # died; a partial shared page is still priced as owned
                # — it is the COW target's budget)
                ent = self.kv.lookup_prefix(
                    prompt, max_pos=int(prompt.size) - 1)
                if ent is not None:
                    npages = max(1, npages
                                 - ent.pos // self.kv.page_size)
            mode = shed_mode()
            if (deadline_ms is not None and mode != "off"
                    and self._ewma_step is not None):
                projected = self._ewma_step * (len(self._queue) + 1)
                if projected * 1e3 > float(deadline_ms):
                    self._reject(
                        "deadline",
                        f"projected first-token wait {projected * 1e3:.1f}"
                        f" ms exceeds deadline {deadline_ms:.1f} ms")
            now = self._clock()
            stream = DecodeStream(now)
            deadline = (now + float(deadline_ms) / 1e3
                        if deadline_ms is not None else None)
            req = _Request(prompt, mn, eos, stream, deadline, npages,
                           self._seq, need_tokens=need_tokens)
            self._seq += 1
            if self.admission and not self.kv.reserve(req, npages):
                self._reject(
                    "kvcache",
                    f"KV page pool exhausted: need {npages} page(s), "
                    f"{self.kv.free_pages()} free of "
                    f"{self.kv.num_pages - 1}")
            self._queue.append(req)
            self.stats["submitted"] += 1
            self._work.notify_all()
            return stream

    # ---------------- scheduling ----------------
    def _bucket_for(self, n: int) -> int:
        for b in self._ladder:
            if b >= n:
                return b
        return self._ladder[-1]

    def _bucket(self) -> int:
        hi = max((s + 1 for s in range(self.slots)
                  if self._occupant[s] is not None), default=1)
        return self._bucket_for(hi)

    def _refill(self):
        if self.static:
            # whole-batch barrier: admit a new batch only once every
            # slot is free (the baseline the bench A/Bs against)
            if any(o is not None for o in self._occupant):
                return
        ps = self.kv.page_size
        for slot in range(self.slots):
            if not self._queue:
                break
            if self._occupant[slot] is not None:
                continue
            req = self._queue[0]
            tot = (pages_needed(req.need_tokens, ps)
                   if req.need_tokens else req.npages)
            ent = None
            if self._prefix_share and req.prompt.size > 1:
                # seat-time lookup (the authoritative one — the
                # submit-time lookup only priced admission); cap leaves
                # >= 1 prompt token to prefill so the final chunk still
                # produces the request's first output token
                ent = self.kv.lookup_prefix(
                    req.prompt, max_pos=int(req.prompt.size) - 1)
            if ent is not None:
                shared = list(ent.pages)
                own_n = max(0, tot - len(shared))
                own = self.kv.alloc(req, own_n) if own_n else []
                if own is None:      # admission=False path: wait
                    break
                self.kv.share(req, shared)
                # reservation correction: keep ONE spare page when the
                # last shared page is partial — the COW target for the
                # first divergent write into it
                self.kv.trim_reservation(req, 1 if ent.pos % ps else 0)
                pages = shared + list(own)
                self._device_len[slot] = ent.pos
                req.pos = ent.pos
                req.shared_len = ent.pos
                if ent.state is not None:
                    self._h = self._h.at[slot].set(ent.state[0])
                    self._c = self._c.at[slot].set(ent.state[1])
                self.stats["prefix_hits"] += 1
                self.stats["prefix_tokens"] += ent.pos
            else:
                pages = self.kv.alloc(req, tot)
                if pages is None:    # admission=False path: wait
                    break
                self._device_len[slot] = 0
            self._queue.popleft()
            req.slot = slot
            req.phase = "prefill"
            self._occupant[slot] = req
            self._table[slot, :] = 0
            self._table[slot, :len(pages)] = pages
        self._m_active.set(sum(1 for o in self._occupant
                               if o is not None))

    def _plan(self):
        occ = self._occupant
        pre = [s for s in range(self.slots)
               if occ[s] is not None and occ[s].phase == "prefill"]
        dec = [s for s in range(self.slots)
               if occ[s] is not None and occ[s].phase == "decode"
               and not occ[s].done]
        kind = "decode"
        if self._spec_k:
            # speculative mode: a slot joins a verify step only once
            # its FIRST token has retired (the drafter proposes from
            # host history) and its previous verify is out of flight —
            # the window drain is the per-slot sync point, so a slot
            # never has two verifies speculating past each other
            kind = "verify"
            dec = [s for s in dec if not occ[s].inflight
                   and occ[s].generated >= 1]
        if self.static:
            if pre:
                return "prefill", min(pre, key=lambda s: occ[s].seq)
            if dec:
                return kind, dec
            return None, None
        # continuous: strict alternation — prefill may never run twice
        # in a row while decode work exists (the non-starvation rule)
        if pre and (not dec or not self._last_was_prefill):
            return "prefill", min(pre, key=lambda s: occ[s].seq)
        if dec:
            return kind, dec
        return None, None

    def step_once(self) -> bool:
        """One scheduler iteration: refill free slots, dispatch ONE
        compiled program (a decode step over every active slot, or one
        prefill chunk), push it into the window. False when there is no
        work. The manual-driving hook for deterministic tests; the
        background loop calls exactly this."""
        with self._lock:
            if self._dead is not None:
                return False
            self._refill()
            kind, what = self._plan()
            if kind is None:
                return False
            try:
                if kind == "prefill":
                    self._dispatch_prefill(what)
                elif kind == "verify":
                    self._dispatch_verify(what)
                else:
                    self._dispatch_decode(what)
            except MXNetError as e:
                self._fail_all(e)
                return False
            return True

    def sync(self):
        """Retire every in-flight step (the blessed waits) — delivers
        all tokens computed so far to their streams."""
        with self._lock:
            if len(self._window):
                self._window.drain()

    def _stitch(self, b: int, h2, c2, nxt, kp, vp):
        """Fold one bucket's outputs back into the full-slot device
        arrays (device-side chaining: no host round trip)."""
        self.kv.k_pages._data = kp
        self.kv.v_pages._data = vp
        if b == self.slots:
            self._h, self._c = h2, c2
            return nxt
        self._h = jnp.concatenate([h2, self._h[b:]], axis=0)
        self._c = jnp.concatenate([c2, self._c[b:]], axis=0)
        return None

    def _push(self, meta: tuple, arr):
        self._tag += 1
        self._window.push((meta, arr), tag=f"{meta[0]}#{self._tag}")

    def _cow_guard(self, slot: int, req: _Request, start: int, n: int):
        """Copy-on-write fence: before a dispatch writes device
        positions ``[start, start + n)``, give the slot private copies
        of every page in the write range still shared with another
        request (one async device-side page copy each; the table row
        repoints to the copy). MUST run before the dispatch snapshots
        the table into program arguments."""
        if not self._prefix_share or n <= 0:
            return
        ps = self.kv.page_size
        for pi in range(start // ps, (start + n - 1) // ps + 1):
            page = int(self._table[slot, pi])
            if page and self.kv.page_shared(page):
                self._table[slot, pi] = self.kv.cow(req, page)

    def _dispatch_decode(self, slots_active: List[int]):
        b = self._bucket()
        ps = self.kv.page_size
        pidx = onp.zeros(b, onp.int32)
        poff = onp.zeros(b, onp.int32)
        lengths = onp.ones(b, onp.int32)
        act = onp.zeros(b, bool)
        metas = []
        for s in slots_active:
            dl = int(self._device_len[s])
            self._cow_guard(s, self._occupant[s], dl, 1)
            pidx[s] = self._table[s, dl // ps]
            poff[s] = dl % ps
            lengths[s] = dl + 1
            act[s] = True
            metas.append((s, self._occupant[s]))
            self._device_len[s] += 1
        entry = self._entry("decode", b)
        args = (self.model.params, self._tokens_dev[:b], self._h[:b],
                self._c[:b], self.kv.k_pages._data,
                self.kv.v_pages._data, jnp.asarray(pidx),
                jnp.asarray(poff), jnp.asarray(self._table[:b]),
                jnp.asarray(lengths), jnp.asarray(act))
        with _tguard.hot_scope("DecodeEngine.decode_step"):
            nxt, h2, c2, kp, vp = self._call(entry, args)
        full = self._stitch(b, h2, c2, nxt, kp, vp)
        self._tokens_dev = full if full is not None else \
            jnp.concatenate([nxt, self._tokens_dev[b:]])
        self.stats["steps"] += 1
        self._last_was_prefill = False
        self._push(("decode", metas, self._clock()), nxt)

    def _dispatch_prefill(self, slot: int):
        req = self._occupant[slot]
        b = self._bucket()
        C = self._chunk
        n_valid = min(C, req.prompt.size - req.pos)
        toks = onp.zeros((b, C), onp.int32)
        toks[slot, :n_valid] = req.prompt[req.pos:req.pos + n_valid]
        start = onp.zeros(b, onp.int32)
        start[slot] = self._device_len[slot]
        nv = onp.zeros(b, onp.int32)
        nv[slot] = n_valid
        reset = onp.zeros(b, bool)
        reset[slot] = req.pos == 0
        act = onp.zeros(b, bool)
        act[slot] = True
        self._cow_guard(slot, req, int(start[slot]), n_valid)
        entry = self._entry("prefill", b)
        args = (self.model.params, jnp.asarray(toks), self._h[:b],
                self._c[:b], self.kv.k_pages._data,
                self.kv.v_pages._data, jnp.asarray(start),
                jnp.asarray(nv), jnp.asarray(reset), jnp.asarray(act),
                jnp.asarray(self._table[:b]))
        with _tguard.hot_scope("DecodeEngine.prefill_chunk"):
            nxt, h2, c2, kp, vp = self._call(entry, args)
        full = self._stitch(b, h2, c2, None, kp, vp)
        self._device_len[slot] += n_valid
        req.pos += n_valid
        final = req.pos >= req.prompt.size
        if final:
            # the slot joins the decode batch NEXT iteration; its first
            # token chains device-side (async) into the token array
            req.phase = "decode"
            self._tokens_dev = self._tokens_dev.at[slot].set(nxt[slot])
        reg = None
        if self._prefix_share:
            # snapshot NOW (post-stitch the state rows are exactly the
            # post-chunk state; by retire time they may have advanced):
            # the registry commits tokens[:pos] -> pages + state at
            # retire, once the writes are known good
            npg = pages_needed(req.pos, self.kv.page_size)
            reg = (onp.ascontiguousarray(req.prompt[:req.pos]),
                   req.pos,
                   [int(p) for p in self._table[slot, :npg]],
                   (self._h[slot], self._c[slot]))
        self.stats["prefill_chunks"] += 1
        self._last_was_prefill = True
        self._push(("prefill", slot, req, final, self._clock(), reg),
                   nxt)

    def _dispatch_verify(self, slots_active: List[int]):
        b = self._bucket()
        ps = self.kv.page_size
        K = self._spec_k + 1
        toks = onp.zeros((b, K), onp.int32)
        start = onp.zeros(b, onp.int32)
        nd = onp.ones(b, onp.int32)
        act = onp.zeros(b, bool)
        metas = []
        for s in slots_active:
            req = self._occupant[s]
            dl = int(self._device_len[s])
            # never draft past the request's token budget or its page
            # table (the admission slack covers spec_k positions)
            room = self.max_pages_per_slot * ps - dl - 1
            left = req.max_new - req.generated - 1
            k_prop = max(0, min(self._spec_k, left, room))
            drafts = (list(self._drafter.propose(req.history,
                                                 k_prop))[:k_prop]
                      if k_prop else [])
            n = 1 + len(drafts)
            toks[s, 0] = req.history[-1]
            if drafts:
                toks[s, 1:n] = drafts
            start[s] = dl
            nd[s] = n
            act[s] = True
            req.inflight = True
            self._cow_guard(s, req, dl, n)
            metas.append((s, req, n))
        entry = self._entry("verify", b)
        args = (self.model.params, jnp.asarray(toks), self._h[:b],
                self._c[:b], self.kv.k_pages._data,
                self.kv.v_pages._data, jnp.asarray(start),
                jnp.asarray(nd), jnp.asarray(act),
                jnp.asarray(self._table[:b]))
        with _tguard.hot_scope("DecodeEngine.verify_step"):
            emitted, n_acc, nxt, h2, c2, kp, vp = self._call(entry, args)
        full = self._stitch(b, h2, c2, nxt, kp, vp)
        self._tokens_dev = full if full is not None else \
            jnp.concatenate([nxt, self._tokens_dev[b:]])
        self.stats["steps"] += 1
        self.stats["spec_steps"] += 1
        self._last_was_prefill = False
        self._push(("verify", metas, self._clock()), (emitted, n_acc))

    # ---------------- retire (the one blessed sync) ----------------
    def _retire_sync(self, payload):
        meta, arr = payload
        now = self._clock()          # blessed: runs under the window's
        if meta[0] == "decode":      # allow_transfers at retire
            toks = onp.asarray(arr)
            _, pairs, t0 = meta
            dt = max(0.0, now - t0)
            self._ewma_step = dt if self._ewma_step is None \
                else 0.8 * self._ewma_step + 0.2 * dt
            for slot, req in pairs:
                if req.done:
                    continue
                self._deliver(slot, req, int(toks[slot]), now)
        elif meta[0] == "verify":
            emitted = onp.asarray(arr[0])
            n_acc = onp.asarray(arr[1])
            toks = emitted
            _, triples, t0 = meta
            dt = max(0.0, now - t0)
            self._ewma_step = dt if self._ewma_step is None \
                else 0.8 * self._ewma_step + 0.2 * dt
            for slot, req, n in triples:
                req.inflight = False
                if req.done:
                    continue
                a = max(1, min(int(n_acc[slot]), n))
                # KV commit is pure length bookkeeping: the verify
                # already wrote positions [dl, dl+n); attention masks
                # by lengths, so the rejected tail is plain garbage
                # that a later step overwrites
                self._device_len[slot] += a
                drafted, accepted = n - 1, a - 1
                self.stats["spec_drafted"] += drafted
                self.stats["spec_accepted"] += accepted
                hist = self.stats["accept_hist"]
                hist[a] = hist.get(a, 0) + 1
                if drafted:
                    self._m_drafted.inc(drafted)
                if accepted:
                    self._m_accepted.inc(accepted)
                req.stream._record_step(a, drafted, accepted)
                for t in range(a):
                    self._deliver(slot, req, int(emitted[slot, t]), now)
                    if req.done:
                        break
        else:
            toks = onp.asarray(arr)
            _, slot, req, final, _t0, reg = meta
            if reg is not None and not req.done:
                toks_r, pos_r, pages_r, state_r = reg
                self.kv.register_prefix(toks_r, pos_r, pages_r,
                                        state=state_r)
            if final and not req.done:
                self._deliver(slot, req, int(toks[slot]), now)
        shared = self.kv.shared_pages()
        if shared > self.stats["kv_shared_peak"]:
            self.stats["kv_shared_peak"] = shared
        util = self.kv.utilization()
        if util > self.stats["kv_util_peak"]:
            self.stats["kv_util_peak"] = util
        return toks

    def _deliver(self, slot: int, req: _Request, tok: int, now: float):
        first = req.generated == 0
        req.generated += 1
        req.history.append(int(tok))
        req.stream._deliver(tok, now)
        self.stats["tokens"] += 1
        self._m_tokens.inc()
        if first:
            self._m_ttft.observe(max(0.0, now - req.t_submit))
        else:
            gap = max(0.0, now - req.t_last_tok)
            self._m_tpot.observe(gap)
            self._ewma_tpot = gap if self._ewma_tpot is None \
                else 0.8 * self._ewma_tpot + 0.2 * gap
        req.t_last_tok = now
        if req.deadline is not None and now > req.deadline:
            self.stats["deadline_missed"] += 1
            self._finish_slot(slot, req, DeadlineExceeded(
                f"decode request missed its deadline after "
                f"{req.generated} token(s)"))
            return
        eos = req.eos if req.eos is not None else self.eos_id
        if (eos is not None and tok == eos) or \
                req.generated >= req.max_new:
            self._finish_slot(slot, req, None)
            return
        # per-token deadline re-projection: when the TPOT EWMA says the
        # REMAINING tokens cannot land inside the deadline, shed the
        # stream NOW — its KV pages free immediately for streams that
        # can still make their budget, instead of decoding tokens the
        # client will throw away at the reactive check above
        left = req.max_new - req.generated
        if req.deadline is not None and self._ewma_tpot is not None \
                and now + left * self._ewma_tpot > req.deadline:
            self.stats["deadline_missed"] += 1
            self.stats["shed_midstream"] += 1
            self._finish_slot(slot, req, DeadlineExceeded(
                f"decode stream shed mid-flight after {req.generated} "
                f"token(s): projected remaining decode time "
                f"({left} x {self._ewma_tpot * 1e3:.2f} ms TPOT) "
                f"overruns the deadline — KV pages freed for streams "
                f"that can still finish in budget"))

    def _finish_slot(self, slot: int, req: _Request,
                     exc: Optional[BaseException]):
        req.done = True
        if self._occupant[slot] is req:
            self._occupant[slot] = None
            self._table[slot, :] = 0
        self.kv.release(req)
        if exc is None:
            self.stats["completed"] += 1
            req.stream._finish()
        else:
            req.stream._fail(exc)
        self._m_active.set(sum(1 for o in self._occupant
                               if o is not None))
        self._work.notify_all()

    def _fail_all(self, exc: BaseException):
        self._dead = exc
        self._window.abandon()
        for slot in range(self.slots):
            req = self._occupant[slot]
            if req is not None and not req.done:
                req.done = True
                self.kv.release(req)
                req.stream._fail(exc)
            self._occupant[slot] = None
        while self._queue:
            req = self._queue.popleft()
            self.kv.release(req)
            req.stream._fail(exc)
        self._m_active.set(0)

    # ---------------- lifecycle ----------------
    def _idle(self) -> bool:
        return (not self._queue and len(self._window) == 0
                and all(o is None for o in self._occupant))

    def _serve_loop(self):
        while not self._stop.is_set():
            did = self.step_once()
            if did:
                continue
            with self._lock:
                if len(self._window):
                    try:
                        self._window.drain()
                    except MXNetError as e:
                        self._fail_all(e)
                    continue
            with self._work:
                self._work.wait(0.002)

    def drain(self, timeout: float = 60.0) -> bool:
        """Stop admitting (subsequent submits shed with
        ``reason="draining"``) and run every accepted request to
        completion. True when fully drained."""
        with self._lock:
            self._draining = True
            self._work.notify_all()
        if self._thread is not None:
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                with self._lock:
                    if self._idle() or self._dead is not None:
                        return self._dead is None
                time.sleep(0.002)
            return False
        while True:
            if self.step_once():
                continue
            with self._lock:
                if len(self._window):
                    try:
                        self._window.drain()
                    except MXNetError as e:
                        self._fail_all(e)
                        return False
                    continue
                return self._idle()

    def close(self, timeout: float = 5.0):
        """Drain the window, fail anything still queued with a typed
        ``ServingShutdown``, stop the dispatch thread."""
        self._stop.set()
        with self._work:
            self._work.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None
        with self._lock:
            try:
                if len(self._window):
                    self._window.drain()
            except MXNetError:
                self._window.abandon()
            if self._dead is None:
                exc = ServingShutdown("DecodeEngine closed")
                for slot in range(self.slots):
                    req = self._occupant[slot]
                    if req is not None and not req.done:
                        req.done = True
                        self.kv.release(req)
                        req.stream._fail(exc)
                    self._occupant[slot] = None
                while self._queue:
                    req = self._queue.popleft()
                    self.kv.release(req)
                    req.stream._fail(exc)
                self._dead = exc
                self._m_active.set(0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


# ---------------------------------------------------------------------------
# bench harness: continuous vs static A/B
# ---------------------------------------------------------------------------

def run_decode(model, prompts, max_new, *, static: bool = False,
               ladder: Optional[Sequence[int]] = None,
               page_size: Optional[int] = None,
               eos_id: Optional[int] = None, inflight: int = 1,
               warmup: bool = True, spec_k: Optional[int] = None,
               prefix_share: Optional[bool] = None,
               drafter=None) -> dict:
    """Submit every request up front and drive the engine to
    completion (``chip_smoke.py``'s decode phase drives it). ``static``
    selects the whole-batch baseline policy; everything else (model,
    compiled programs, kernels, page geometry) is identical, so the
    delta is pure scheduling."""
    prompts = [onp.asarray(p, onp.int32).ravel() for p in prompts]
    mns = ([int(max_new)] * len(prompts) if isinstance(max_new, int)
           else [int(m) for m in max_new])
    sk = (globals()["spec_k"]() if spec_k is None
          else max(0, int(spec_k)))
    slack = max(1, int(inflight)) + sk
    ps = int(page_size) if page_size else kv_page_size()
    mc = max(int(p.size) + m + slack for p, m in zip(prompts, mns))
    # size the pool so every request can hold its reservation at once:
    # the A/B measures scheduling, not page starvation
    total_pages = 1 + sum(pages_needed(p.size + m + slack, ps)
                          for p, m in zip(prompts, mns))
    eng = DecodeEngine(model, ladder=ladder, num_pages=total_pages,
                       page_size=ps, max_context=mc, eos_id=eos_id,
                       inflight=inflight, depth=len(prompts) + 1,
                       static=static, start=False, spec_k=sk,
                       prefix_share=prefix_share, drafter=drafter)
    try:
        if warmup:
            eng.warmup()
        t0 = time.perf_counter()
        streams = [eng.submit(p, max_new=m)
                   for p, m in zip(prompts, mns)]
        eng.drain()
        wall = time.perf_counter() - t0
        recs = [s.record() for s in streams]
        tokens = sum(r["tokens"] for r in recs)
        from . import loadgen
        out = {
            "mode": "static" if static else "continuous",
            "requests": len(prompts),
            "tokens": int(tokens),
            "wall_s": round(wall, 4),
            "decode_tokens_per_sec": round(tokens / wall, 2)
            if wall > 0 else None,
            "steps": eng.stats["steps"],
            "prefill_chunks": eng.stats["prefill_chunks"],
            "kv_page_util": round(eng.stats["kv_util_peak"], 4),
            "kv_num_pages": eng.kv.num_pages,
            "slot_ladder": list(eng._ladder),
            "page_size": ps,
            # what each request emitted: the speculative path's contract
            # is token-for-token equality with plain greedy
            "token_ids": [[int(t) for t in s.result(0)] for s in streams],
        }
        if eng._spec_k:
            st = eng.stats
            out["spec_k"] = eng._spec_k
            out["spec_steps"] = st["spec_steps"]
            out["spec_drafted"] = st["spec_drafted"]
            out["spec_accepted"] = st["spec_accepted"]
            out["accept_hist"] = dict(st["accept_hist"])
        if eng._prefix_share:
            kvs = eng.kv.stats()
            out["prefix_hits"] = kvs["prefix_hits"]
            out["cow_copies"] = kvs["cow_copies"]
            out["kv_shared_peak"] = eng.stats["kv_shared_peak"]
        out.update(loadgen.streaming_summary(recs, wall))
        return out
    finally:
        eng.close()
