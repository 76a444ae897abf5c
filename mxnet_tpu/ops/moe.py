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
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["moe_gating", "moe_ffn"]


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
