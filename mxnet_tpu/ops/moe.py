"""Mixture-of-Experts FFN with expert parallelism (EP).

No reference analog — the reference has no MoE or expert parallelism
(SURVEY §2.3: TP/PP/EP/SP/CP absent); this is a TPU-native extension in the
same spirit as ring attention (ops/attention.py): the idiomatic scale-out
answer for sparse-expert models.

Design (the standard TPU MoE recipe — GShard/Switch style):
- gating: softmax router, top-k expert choice per token, capacity-bounded
  dispatch (capacity = factor * tokens * k / num_experts). Tokens beyond an
  expert's capacity are dropped (their combine weight is zero), keeping all
  shapes static for XLA.
- dense path: dispatch/combine as one-hot einsums onto (E, C, d) buffers,
  experts run as ONE batched einsum over the expert dimension — MXU-friendly,
  no scalar loops.
- EP path (``axis_name``): experts sharded over an 'ep' mesh axis inside
  shard_map. Each device routes its local tokens to ALL experts, then a
  ``lax.all_to_all`` exchanges dispatch buffers so each device holds only its
  local experts' work; a second all_to_all returns expert outputs for the
  combine. The two all-to-alls ride ICI — this is the EP collective pattern.

Everything is differentiable (einsums + where), so jax.grad flows through
router and experts.

The DROPLESS layer (``moe_route`` / ``moe_experts`` / ``moe_combine``,
behind ``gluon.nn.SparseMoE``) is a different layer, not a second path of
the one above: no capacity and no dropped token at any imbalance, weights
over the chosen experts only, gated experts (ReGLU or SwiGLU) or experts
without a gate (``W_down relu(W_up x)^2``), and a layer that is told WHICH
experts it holds. It routes over all ``E``
experts and computes its own part of the sum:

- ``moe_route``: router logits, the top-k choice and the chosen experts'
  weights in float32, by the model's score rule (``SCORES``: a softmax
  of the chosen logits, or sigmoid scores with a bias that picks but
  never weighs, normalised over the chosen and scaled); the token-expert
  pairs whose expert is held, sorted by expert (a stable argsort; pairs
  of experts held elsewhere sort behind them), each pair's place in that
  order, and the held experts' group sizes;
- ``moe_experts``: the sorted pairs' tokens gathered, one grouped (ragged)
  matrix product a projection over the experts held, the gate's
  activation between. The product is told the group sizes, so rows past
  the last group are never multiplied (two tiers, below);
- ``moe_combine``: each token's chosen outputs weighted and summed in
  float32, the sort's inverse.

Shapes are static: the sorted list has ``N * min(top_k, held)`` rows, the
most pairs the held experts can be given; the pairs they ARE given stand
in its first ``sizes.sum()`` rows. Nothing may count on what the list
holds past them. The gather in front of the products (``_dispatch``,
``x[order // k]``) runs over all those rows. The three passes behind them
(the weighted sum back in ``moe_combine``, its gradient, and the gradient
of ``_dispatch``, which autodiff would scatter-add) have two tiers behind
the kernel layer's gate (``ops/kernels`` ``dispatch("moe_rows")``, from
platform and shapes):

- on one TPU chip the kernels of ``ops/kernels/moe_rows.py``, which read
  the held total on the device and move the held pairs' rows only: a sum
  of the live rows into their tokens (``moe_combine``, ``_dispatch_bwd``)
  and a gather that walks the live prefix of the list (``_combine_bwd``,
  with each pair's weight gradient in the same pass); the rest of the
  list is zero or not written at all;
- everywhere else, under a multi-device mesh, and as the oracle: the XLA
  forms below, gathers through ``order`` and ``place`` over all the rows
  of the list, masked to the held pairs.

``mx_moe_row_mover_total{tier}`` counts which tier a traced call site
took.

The grouped products themselves have the same two tiers behind
``dispatch("grouped_dot")``, from platform, shapes and dtypes:

- on one TPU chip the kernels of ``ops/kernels/grouped_dot.py`` (bf16
  and float32; float32 in one bf16 pass or at highest, as
  ``jax.default_matmul_precision`` asked where the layer was called,
  forward and backward), whose grid is as long as the groups: the three forward
  products, and a backward written out (``_experts_bwd``) in which the
  input's gradient is ONE product, the gate's and the up projection's
  cotangents summed in its accumulator, where autodiff makes two arrays
  and adds them over the static list. The walk over the groups is made
  once a layer and shared by its eight products (five without a gate:
  two forward, three backward);
- everywhere else, under a multi-device mesh, for widths that are no
  multiple of 128 (experts without a gate are zero-padded to one on a
  kernel tier, ``_whole_lane_tiles``), and as the oracle:
  ``lax.ragged_dot`` and autodiff.

Past the last group the kernels write NOTHING (``ragged_dot`` leaves
zeros): what ``moe_experts`` returns there, forward and backward, is
undefined on every tier. Its readers do not read it: ``moe_combine`` and
``_dispatch_bwd`` select the held pairs' rows (kernels) or mask by
``place < sizes.sum()`` (``_pair_rows``), and the matrices' gradients
mask both operands a group. ``mx_moe_grouped_dot_total{tier}`` counts
the traced product sites by tier.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["moe_gating", "moe_ffn", "moe_route", "moe_experts",
           "moe_combine", "routing_counts", "SCORES", "ACTIVATIONS"]

#: the score rules ``moe_route`` knows, as ``mx_moe_router_total`` labels
#: them
SCORES = ("softmax", "sigmoid")
#: the activations of ``moe_experts`` by their configs' names: the gate's
#: in ReGLU and SwiGLU experts, the hidden layer's own in experts without
#: a gate (``relu2``: the square of relu)
ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu,
               "relu2": lambda x: jnp.square(jax.nn.relu(x))}


def moe_gating(x, gate_w, num_experts: int, top_k: int = 2,
               capacity: int = 0):
    """Router: returns (dispatch (N,E,C) one-hot, combine (N,E,C) weights,
    aux_loss). ``x`` (N, d); ``gate_w`` (d, E).

    aux_loss is the Switch/GShard load-balance loss: E * sum_e(frac_tokens_e
    * mean_prob_e) — 1.0 when perfectly balanced."""
    n, _ = x.shape
    e = num_experts
    if capacity <= 0:
        capacity = max(1, (n * top_k) // e)
    logits = x @ gate_w                       # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)

    # top-k selection, one expert at a time so positions stay static
    dispatch = jnp.zeros((n, e, capacity), x.dtype)
    combine = jnp.zeros((n, e, capacity), x.dtype)
    masked = probs
    # per-expert fill counters accumulate across the k rounds
    fill = jnp.zeros((e,), jnp.int32)
    routed = jnp.zeros((n, e), x.dtype)  # PRE-capacity assignments
    for _ in range(top_k):
        idx = jnp.argmax(masked, axis=-1)                    # (N,)
        onehot = jax.nn.one_hot(idx, e, dtype=x.dtype)       # (N, E)
        gate_val = jnp.sum(probs * onehot, axis=-1)          # (N,)
        # position of each token within its chosen expert's buffer
        pos_in_e = (jnp.cumsum(onehot, axis=0) - 1.0)        # (N, E)
        pos = jnp.sum(pos_in_e * onehot, axis=-1).astype(jnp.int32) \
            + jnp.sum(fill * onehot.astype(jnp.int32), axis=-1)
        keep = pos < capacity
        pos_c = jnp.clip(pos, 0, capacity - 1)
        slot = jax.nn.one_hot(pos_c, capacity, dtype=x.dtype)  # (N, C)
        d = onehot[:, :, None] * slot[:, None, :] \
            * keep[:, None, None].astype(x.dtype)
        dispatch = dispatch + d
        combine = combine + d * gate_val[:, None, None]
        fill = fill + jnp.sum(onehot, axis=0).astype(jnp.int32)
        routed = routed + onehot
        masked = masked * (1.0 - onehot)                     # exclude chosen

    # load-balance auxiliary (fraction routed vs mean router prob):
    # balanced routing gives frac=k/E and mean_prob=1/E, so
    # E * sum(frac * mean_prob) / k == 1 regardless of E or k. Fractions
    # come from the PRE-capacity router assignments (Switch/GShard): if
    # drops were counted instead, the penalty would plateau exactly when
    # an expert overflows
    frac = jnp.mean(routed, axis=0)                          # (E,)
    mean_prob = jnp.mean(probs, axis=0)                      # (E,)
    aux = e * jnp.sum(frac * mean_prob) / max(top_k, 1)
    return dispatch, combine, aux


def moe_ffn(x, gate_w, w1, w2, top_k: int = 2, capacity_factor: float = 1.25,
            axis_name=None, activation=jax.nn.relu):
    """MoE feed-forward. ``x`` (N, d); ``gate_w`` (d, E);
    ``w1`` (E, d, h); ``w2`` (E, h, d) — under ``axis_name`` these hold the
    LOCAL expert shard (E_local = E / ep_size) and x the local tokens.

    Returns (out (N, d), aux_loss)."""
    n, d = x.shape
    if axis_name is None:
        e = w1.shape[0]
        cap = max(1, int(capacity_factor * n * top_k / e))
        dispatch, combine, aux = moe_gating(x, gate_w, e, top_k, cap)
        expert_in = jnp.einsum("nec,nd->ecd", dispatch, x)
        h = activation(jnp.einsum("ecd,edh->ech", expert_in, w1))
        expert_out = jnp.einsum("ech,ehd->ecd", h, w2)
        out = jnp.einsum("nec,ecd->nd", combine, expert_out)
        return out, aux

    ep = lax.axis_size(axis_name)
    e_local = w1.shape[0]
    e = e_local * ep
    # capacity per (expert, source shard): each source device may route up
    # to cap of its local tokens to each global expert, so every expert's
    # total buffer is ep*cap — static shapes throughout
    cap = max(1, int(capacity_factor * n * top_k / e))
    dispatch, combine, aux = moe_gating(x, gate_w, e, top_k, cap)
    # (N, E, C) -> (ep, E_local, C, d): expert inputs grouped by the device
    # that OWNS each expert
    expert_in = jnp.einsum("nec,nd->ecd", dispatch, x) \
        .reshape(ep, e_local, cap, d)
    # all-to-all #1: chunk i of dim 0 goes to device i; afterwards dim 0
    # indexes the SOURCE device — each device holds its own experts' tokens
    # from every peer
    expert_in = lax.all_to_all(expert_in, axis_name, split_axis=0,
                               concat_axis=0, tiled=False)
    ei = expert_in.transpose(1, 0, 2, 3).reshape(e_local, ep * cap, d)
    h = activation(jnp.einsum("esd,edh->esh", ei, w1))
    eo = jnp.einsum("esh,ehd->esd", h, w2) \
        .reshape(e_local, ep, cap, d).transpose(1, 0, 2, 3)
    # all-to-all #2: return expert outputs to the token-owning devices
    eo = lax.all_to_all(eo, axis_name, split_axis=0, concat_axis=0,
                        tiled=False)
    # dim 0 now indexes expert-owner devices again -> (E, C, d) aligns with
    # this device's local (N, E, C) combine weights
    expert_out = eo.reshape(e, cap, d)
    out = jnp.einsum("nec,ecd->nd", combine, expert_out)
    # aux is computed from local stats; average across shards
    aux = lax.pmean(aux, axis_name)
    return out, aux


# ---------------------------------------------------------------------------
# the dropless layer: route, grouped experts, combine
# ---------------------------------------------------------------------------

def moe_route(x, router_w, top_k: int, held, score: str = "softmax",
              bias=None, scale: float = 1.0, norm_eps: float = 0.0):
    """Router of the dropless layer. ``x`` (N, d); ``router_w`` (E, d),
    all E experts whichever are held; ``held = (first, count)``.

    ``score`` is the rule that chooses and weighs:

    - ``"softmax"``: the top-k logits, weighed by the softmax over those
      k logits;
    - ``"sigmoid"``: scores s = sigmoid(logits); the top-k of s +
      ``bias`` ((E,), a selection bias that picks and never weighs: no
      gradient reaches it), weighed by ``scale * s / (sum of the chosen
      s + norm_eps)`` (LFM2 adds 1e-6; the DeepSeek-V3 family nothing).

    Returns ``(weights, order, place, sizes)``: ``weights`` (N, k) f32;
    ``order`` (rows,)
    int32, the pairs (token * k + choice) of held experts sorted by
    expert, rows = N * min(k, count); ``place`` (N, k) int32, where each
    pair stands in that order (pairs of experts held elsewhere stand at
    or past the held total); ``sizes`` (count,) int32, the pairs each
    held expert was given. Everything in float32 at ``highest``: a logit
    rounded to bf16 moves the top-k choice on near-ties."""
    n = x.shape[0]
    first, count = held
    logits = jnp.einsum("nd,ed->ne", x.astype(jnp.float32),
                        router_w.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    if score == "softmax":
        top_vals, top_idx = lax.top_k(logits, top_k)
        weights = jax.nn.softmax(top_vals, axis=-1)
    elif score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        picked = scores if bias is None else \
            scores + lax.stop_gradient(bias.astype(jnp.float32))[None, :]
        _, top_idx = lax.top_k(lax.stop_gradient(picked), top_k)
        chosen = jnp.take_along_axis(scores, top_idx, axis=1)
        # the product before the sum, and no add where norm_eps is 0: the
        # lowered step text of the cells without an epsilon rests on both
        weights = scale * chosen
        total = jnp.sum(chosen, axis=-1, keepdims=True)
        weights = weights / (total + norm_eps if norm_eps else total)
    else:
        raise ValueError(f"moe_route: no score rule {score!r}; "
                         f"it knows {SCORES}")
    # each pair's sort key: its expert's index among those held, or
    # ``count`` for an expert held elsewhere
    local = top_idx.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    # a pair's place = its group's start + its rank among the group's
    # pairs, by pair index as the stable sort leaves them
    onehot = (key[:, None] == jnp.arange(count + 1)[None, :]) \
        .astype(jnp.int32)
    totals = onehot.sum(axis=0)
    starts = jnp.cumsum(totals) - totals
    rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0), key[:, None],
                               axis=1)[:, 0] - 1
    place = (starts[key] + rank).reshape(n, top_k).astype(jnp.int32)
    return weights, order[:n * min(top_k, count)], place, totals[:count]


def routing_counts(x, router_w, top_k: int, held, **rule):
    """``(pairs per held expert (count,), held share of all N*k pairs)``
    of one routing (``rule``: the score rule's keywords), for tests and
    PERF.md; not part of any step."""
    _, _, _, sizes = moe_route(x, router_w, top_k, held, **rule)
    return sizes, sizes.sum() / (x.shape[0] * top_k)


def _valid_rows(x, sizes):
    """``x`` with the rows past the last group zeroed."""
    keep = jnp.arange(x.shape[0]) < sizes.sum()
    return jnp.where(keep[:, None], x, jnp.zeros_like(x))


def _grouped_dot(lhs, rhs, sizes):
    """One grouped product of the XLA tier, and the oracle: row r of
    ``lhs`` (rows, k) against ``rhs[g]`` (n, k) for the group g that
    holds r; zero past the last group. ``lax.ragged_dot``: XLA is told
    the group sizes and multiplies no row past them. (One TPU chip takes
    ``ops/kernels/grouped_dot.py`` instead: :func:`_experts`.)"""
    return lax.ragged_dot(lhs, rhs.swapaxes(1, 2), sizes,
                          preferred_element_type=lhs.dtype)


def _products_tier(xs, matrices, hidden, precision):
    """The tier of one layer's grouped products, by the kernel layer's
    gate from platform, shapes, dtypes and the matmul precision asked
    for: ``"pallas"`` / ``"interpret"`` (ops/kernels/grouped_dot.py) or
    ``"xla"`` (``lax.ragged_dot``). ``matrices``: ``w_gate`` (None where
    the experts have no gate), ``w_up``, ``w_down``; ``hidden``: the
    experts' width as the products would see it."""
    from .kernels import dispatch, grouped_dot
    why = grouped_dot.supported(
        xs.shape[0], xs.shape[1], hidden, xs.dtype,
        *(w.dtype for w in matrices if w is not None), precision=precision)
    return dispatch("grouped_dot", supported=why is None, reason=why)[0]


def _whole_lane_tiles(w_up, w_down, hidden):
    """Experts without a gate at ``hidden`` lanes: ``w_up`` (count, f, d)
    gains zero rows and ``w_down`` (count, d, f) zero columns. Exact,
    forward and backward: the new lanes of ``act(W_up x)`` meet zeros of
    ``W_down`` and their gradients are dropped with the padding."""
    more = hidden - w_up.shape[1]
    return (jnp.pad(w_up, ((0, 0), (0, more), (0, 0))),
            jnp.pad(w_down, ((0, 0), (0, 0), (0, more))))


def _count_products(sites, tier):
    from .kernels import count_traced
    count_traced("MOE_GROUPED_DOT", "tier", tier, sites)


def _gated(activation):
    """``(gate, up) ->`` what the down projection reads: ``act(gate) *
    up``, or ``act(up)`` where the experts have no gate (``gate`` None)."""
    act = ACTIVATIONS[activation]
    return lambda gate, up: act(up) if gate is None else act(gate) * up


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _experts(activation, kernel, xs, sizes, w_gate, w_up, w_down):
    """``moe_experts`` behind the tokens' gather on a kernel tier: the
    three products (two without a gate, ``w_gate`` None) as
    ``grouped_dot.gmm`` over one walk of the groups.
    ``kernel = (tier, precision)``: ``"pallas"`` or ``"interpret"``, and
    the matmul precision asked for where the layer was called, which its
    backward keeps (as ``ragged_dot``'s does) though it is traced after
    that ``with`` block has closed."""
    return _experts_fwd(activation, kernel, xs, sizes, w_gate, w_up,
                        w_down)[0]


def _kernel_keywords(kernel):
    tier, precision = kernel
    return {"precision": precision, "interpret": tier == "interpret"}


def _experts_fwd(activation, kernel, xs, sizes, w_gate, w_up, w_down):
    from .kernels import grouped_dot as kernels
    _count_products(2 if w_gate is None else 3, kernel[0])
    rows = xs.shape[0]
    walk = kernels.group_metadata(sizes, rows, kernels.row_tile(rows))
    gmm = functools.partial(kernels.gmm, **_kernel_keywords(kernel))
    gate = None if w_gate is None else gmm(xs, w_gate, walk)
    up = gmm(xs, w_up, walk)
    y = gmm(_gated(activation)(gate, up), w_down, walk)
    # the activation's product is made again in the backward, in the
    # pass that takes its gradient, and is no residual of this function
    # (XLA may still merge the two makings and keep it: PERF.md, PR 33)
    return y, (xs, gate, up, walk, w_gate, w_up, w_down)


def _experts_bwd(activation, kernel, res, dy):
    from .kernels import grouped_dot as kernels
    xs, gate, up, walk, w_gate, w_up, w_down = res
    _count_products(3 if gate is None else 5, kernel[0])
    gmm_t = functools.partial(kernels.gmm, transposed=True,
                              **_kernel_keywords(kernel))
    tgmm = functools.partial(kernels.tgmm, **_kernel_keywords(kernel))
    h, pull = jax.vjp(_gated(activation), gate, up)
    d_gate, d_up = pull(gmm_t(dy, w_down, walk))
    if gate is None:
        return (gmm_t(d_up, w_up, walk), None, None, tgmm(d_up, xs, walk),
                tgmm(dy, h, walk))
    # the input's gradient once: both cotangents in one accumulator
    dxs = gmm_t((d_gate, d_up), (w_gate, w_up), walk)
    return (dxs, None, tgmm(d_gate, xs, walk), tgmm(d_up, xs, walk),
            tgmm(dy, h, walk))


_experts.defvjp(_experts_fwd, _experts_bwd)


def _row_movers(n, rows, k, d, *dtypes):
    """What moves the rows of one call site, by the kernel layer's gate
    from platform, shapes and dtypes, counted while the site is traced:
    ``(ops.kernels.moe_rows, its tier as keywords)``, or None for the XLA
    forms below."""
    from .kernels import count_traced, dispatch, moe_rows
    why = moe_rows.rows_supported(n, rows, k, d, *dtypes)
    path, _ = dispatch("moe_rows", supported=why is None, reason=why)
    count_traced("MOE_ROW_MOVER", "tier", path)
    if path == "xla":
        return None
    return moe_rows, {"interpret": path == "interpret"}


@jax.custom_vjp
def _dispatch(x, order, place, sizes):
    """``x[order // k]``: the sorted pairs' tokens. Its gradient is a
    gather too (each token sums its pairs' rows through ``place``), where
    autodiff would scatter-add with repeated indices."""
    return x[order // place.shape[1]]


def _dispatch_fwd(x, order, place, sizes):
    return _dispatch(x, order, place, sizes), (order, place, sizes)


def _pair_rows(rows, place, sizes):
    """(N, k, d): each pair's row of the sorted list, zero for a pair
    whose expert is held elsewhere."""
    held = place < sizes.sum()
    got = rows[jnp.minimum(place, rows.shape[0] - 1)]
    return jnp.where(held[..., None], got, jnp.zeros_like(got))


def _dispatch_bwd(res, g):
    order, place, sizes = res
    n, k = place.shape
    movers = _row_movers(n, g.shape[0], k, g.shape[1], g.dtype)
    if movers is None:
        dx = _pair_rows(g, place, sizes).astype(jnp.float32).sum(axis=1)
        return dx.astype(g.dtype), None, None, None
    kernels, tier = movers
    dx = kernels.scatter_sum(g, order, sizes.sum(), k, n,
                             jnp.ones((n, k), jnp.float32), **tier)
    return dx.astype(g.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def moe_experts(x, order, place, sizes, w_gate, w_up, w_down,
                activation: str = "relu"):
    """The held experts on the sorted pairs. ``x`` (N, d); ``order``,
    ``place``, ``sizes`` from :func:`moe_route`; ``w_gate``, ``w_up``
    (count, f, d) and ``w_down`` (count, d, f), expert e's
    ``y = W_down (act(W_gate x) * (W_up x))``, ``act`` one of
    ``ACTIVATIONS`` (``relu``: ReGLU, ``silu``: SwiGLU); with ``w_gate``
    None the experts have no gate, ``y = W_down act(W_up x)`` (``relu2``),
    two products forward and three backward where the gated form has
    three and five, and on a kernel tier a width that is no multiple of
    128 lanes is zero-padded to the next (the gated form at such a width
    is ``lax.ragged_dot``'s). Returns (rows,
    d), row p the output for pair ``order[p]``; rows past the last group
    are UNDEFINED, and so is their gradient's row (the kernel tier writes
    nothing there, ``ragged_dot`` zeros; ``moe_combine`` and
    ``_dispatch_bwd`` never read them)."""
    xs = _dispatch(x, order, place, sizes)
    precision = jax.config.jax_default_matmul_precision
    hidden = w_up.shape[1]
    if w_gate is None:
        # the next whole number of lane tiles (Nemotron-H's 1856 = 14.5):
        # the kernels take that, and a step's time follows the held pairs
        # a fifth as much as on lax.ragged_dot (PERF.md section 6, PR 35)
        hidden += -hidden % 128
    tier = _products_tier(xs, (w_gate, w_up, w_down), hidden, precision)
    if tier != "xla":
        if hidden != w_up.shape[1]:
            w_up, w_down = _whole_lane_tiles(w_up, w_down, hidden)
        return _experts(activation, (tier, precision), xs, sizes, w_gate,
                        w_up, w_down)
    _count_products(2 if w_gate is None else 3, tier)
    gate = None if w_gate is None else _grouped_dot(xs, w_gate, sizes)
    up = _grouped_dot(xs, w_up, sizes)
    return _grouped_dot(_gated(activation)(gate, up), w_down, sizes)


@jax.custom_vjp
def moe_combine(y, weights, order, place, sizes):
    """Each token's chosen outputs, weighted and summed in float32:
    ``out[n] = sum_j weights[n, j] * y[place[n, j]]`` over the pairs whose
    expert is held. ``y`` (rows, d) from :func:`moe_experts`. Returns
    (N, d) float32. Gathers forward and backward."""
    n, k = place.shape
    movers = _row_movers(n, y.shape[0], k, y.shape[1], y.dtype)
    if movers is None:
        picked = _pair_rows(y, place, sizes).astype(jnp.float32)
        return jnp.einsum("nk,nkd->nd", weights, picked)
    kernels, tier = movers
    return kernels.scatter_sum(y, order, sizes.sum(), k, n, weights, **tier)


def _combine_fwd(y, weights, order, place, sizes):
    return (moe_combine(y, weights, order, place, sizes),
            (y, weights, order, place, sizes))


def _combine_bwd(res, g):
    y, weights, order, place, sizes = res
    n, k = place.shape
    movers = _row_movers(n, y.shape[0], k, y.shape[1], y.dtype, g.dtype)
    # d weights[n, j] = <y[place[n, j]], dout[n]>: taken pair by pair in
    # the sorted list, where dout is gathered already, then one number a
    # pair carried back through ``place``
    if movers is None:
        g_pair = g[order // k]                  # (rows, d): each pair's dout
        dy = _valid_rows((g_pair * weights.reshape(-1)[order][:, None])
                         .astype(y.dtype), sizes)
        dw_pair = jnp.sum(y.astype(jnp.float32) * g_pair, axis=-1)
    else:
        kernels, tier = movers
        dy, dw_pair = kernels.gather_rows(g, order, sizes.sum(), k, weights,
                                          y, **tier)
    dw = jnp.where(place < sizes.sum(),
                   dw_pair[jnp.minimum(place, y.shape[0] - 1)], 0.0)
    return dy, dw.astype(weights.dtype), None, None, None


moe_combine.defvjp(_combine_fwd, _combine_bwd)


# ---------------------------------------------------------------------------
# sharding spec pack (analysis/sharding.py expect_spec)
# ---------------------------------------------------------------------------
# Expert parallelism's contract, declared next to the implementation:
# exactly the two all-to-alls above (dispatch out, combine back) per
# application on the 'ep' axis — a THIRD exchange or any all-gather
# above the floor means tokens or expert weights are leaving the
# expert-sharded layout; the aux-loss pmean is a declared reduction;
# and the expert weights (w1/w2, leading dim 'ep'-sharded) must
# actually live at ~1/ep per device (the state-budget check over the
# sharding table).
try:
    from ..analysis import sharding as _asharding

    MOE_EP_SPEC_PACK = _asharding.register_spec_pack(
        _asharding.SpecPack(
            name="ep-moe",
            description="expert-parallel MoE FFN (dispatch/combine "
                        "all-to-all pair over 'ep', GShard/Switch "
                        "capacity-bounded routing)",
            axes=("ep",),
            rules=(_asharding.CollectiveRule(
                "all_to_all", axis="ep", min_count=2),),
            declared=(_asharding.CollectiveRule("all_reduce",
                                                axis="ep"),),
            state_axis="ep"))
except Exception:                        # pragma: no cover - defensive
    pass
