"""Pipeline parallelism: GPipe-style microbatch schedule over a 'pp' mesh
axis.

No reference analog (SURVEY §2.3: pipeline parallelism absent upstream —
the reference only had manual per-ctx layer placement with cross-device
copies, model_parallel_lstm.md). TPU-native design: each device along the
``pp`` axis owns ONE stage's weights; microbatches stream through the ring
with ``lax.ppermute`` hops, so stage s computes microbatch m at tick
t = s + m — the classic GPipe fill/drain schedule, expressed as a
``lax.scan`` inside ``shard_map`` (differentiable end-to-end: reverse-mode
through scan + ppermute gives the 1F1B-equivalent backward automatically).

Uniform activation shape across stages is required (the transformer/MLP
case); a stage is any ``fn(stage_params, x) -> y`` with y.shape == x.shape.

``double_buffer=True`` switches to a one-slot-delay schedule that holds
TWO ring carries: the hop launched at tick t is not consumed until tick
t+2, so the collective-permute of microbatch m's activations is in
flight while the stage computes microbatch m+1 — the permute latency
hides behind compute instead of sitting on the critical path between
ticks.  The price is a deeper fill/drain bubble (2·(pp-1) ticks instead
of pp-1); per-microbatch results are bit-identical either way, only the
schedule changes.  Default comes from ``MXNET_PIPELINE_DOUBLE_BUFFER``.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError

__all__ = ["pipeline_apply", "run_pipeline"]


def _double_buffer_default() -> bool:
    return os.environ.get("MXNET_PIPELINE_DOUBLE_BUFFER", "0").lower() in (
        "1", "true", "yes", "on")


def pipeline_apply(stage_fn, stage_params, microbatches, axis_name="pp",
                   double_buffer=None):
    """Run inside shard_map over ``axis_name``. ``stage_params`` are THIS
    device's stage weights; ``microbatches`` (M, mb, ...) the full
    replicated stream. Returns (M, mb, ...) outputs, replicated (last
    stage's results psum-broadcast). ``double_buffer`` selects the
    latency-hiding one-slot-delay hop schedule (None → the
    ``MXNET_PIPELINE_DOUBLE_BUFFER`` env default)."""
    if double_buffer is None:
        double_buffer = _double_buffer_default()
    pp = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    m_count = microbatches.shape[0]
    mb_shape = microbatches.shape[1:]
    perm = [(i, (i + 1) % pp) for i in range(pp)]
    # hop latency in ticks: 1 for the classic GPipe ring (a hop launched
    # at tick t is eaten at t+1, serializing permute after compute), 2
    # when double-buffered (the hop rides a second carry slot for one
    # extra tick, so it permutes WHILE tick t+1 computes)
    lat = 2 if double_buffer else 1

    def tick(carry_out, t):
        ready, inflight, outputs = carry_out
        # stage 0 ingests microbatch t (while it exists); later stages eat
        # the ring carry from their predecessor
        inp = jnp.where(idx == 0,
                        microbatches[jnp.clip(t, 0, m_count - 1)], ready)
        out = stage_fn(stage_params, inp)
        # the last stage emits microbatch j = t - lat*(pp-1) once the
        # pipe fills
        j = t - lat * (pp - 1)
        outputs = jnp.where((idx == pp - 1) & (j >= 0),
                            outputs.at[jnp.clip(j, 0, m_count - 1)].set(out),
                            outputs)
        hop = lax.ppermute(out, axis_name, perm)
        if double_buffer:
            # this tick's hop parks in the inflight slot; the PREVIOUS
            # tick's hop (already a full compute tick in flight) becomes
            # next tick's input
            return (inflight, hop, outputs), None
        return (hop, inflight, outputs), None

    def _varying(a):
        # the ring carry differs per device; mark the initial zeros as
        # pp-varying so scan's carry types line up (JAX VMA tracking)
        return lax.pcast(a, (axis_name,), to="varying")

    init = (_varying(jnp.zeros(mb_shape, microbatches.dtype)),
            _varying(jnp.zeros(mb_shape, microbatches.dtype)),
            _varying(jnp.zeros((m_count,) + mb_shape, microbatches.dtype)))
    (_, _, outputs), _ = lax.scan(tick, init,
                                  jnp.arange(m_count + lat * (pp - 1)))
    # broadcast the last stage's buffer to every device so callers can use
    # replicated out_specs
    return lax.psum(jnp.where(idx == pp - 1, outputs,
                              jnp.zeros_like(outputs)), axis_name)


def run_pipeline(stage_fn, stacked_params, x, num_microbatches, mesh,
                 axis_name="pp", double_buffer=None):
    """Convenience wrapper: shard ``stacked_params`` (leading dim = number
    of stages) over ``axis_name`` of ``mesh``, split batch ``x`` into
    ``num_microbatches``, run the pipeline, return (B, ...) outputs."""
    from jax.sharding import PartitionSpec as P
    pp = mesh.shape[axis_name]
    b = x.shape[0]
    if b % num_microbatches:
        raise MXNetError(
            f"batch {b} not divisible into {num_microbatches} microbatches")
    for leaf in jax.tree_util.tree_leaves(stacked_params):
        if leaf.shape[0] != pp:
            raise MXNetError(
                f"stacked_params leading dim {leaf.shape[0]} != pipeline "
                f"size {pp} (one stage per '{axis_name}' device)")
    micro = x.reshape(num_microbatches, b // num_microbatches, *x.shape[1:])

    def shard_fn(params_local, micro_all):
        params_local = jax.tree_util.tree_map(lambda a: a[0], params_local)
        return pipeline_apply(stage_fn, params_local, micro_all, axis_name,
                              double_buffer=double_buffer)

    from .collectives import shard_map as _compat_shard_map
    out = _compat_shard_map(
        shard_fn, mesh,
        (P(axis_name), P()), P())(stacked_params, micro)
    return out.reshape(b, *out.shape[2:])


# ---------------------------------------------------------------------------
# sharding spec pack (analysis/sharding.py expect_spec)
# ---------------------------------------------------------------------------
# The GPipe schedule's contract, declared next to the implementation:
# microbatches hop the ring with lax.ppermute (>= 1 collective-permute
# on 'pp' — XLA fuses the scan body's hop into one op) and the last
# stage's outputs broadcast back with ONE psum (>= 1 all-reduce); the
# stage weights (leading dim 'pp'-sharded by run_pipeline) must live at
# ~1/pp per device.  An all-gather above the floor means a stage pulled
# another stage's weights or activations — the cross-stage
# materialization pipelining exists to avoid.
try:
    from ..analysis import sharding as _asharding

    PIPELINE_SPEC_PACK = _asharding.register_spec_pack(
        _asharding.SpecPack(
            name="pp-gpipe",
            description="GPipe microbatch pipeline (ppermute ring hops "
                        "+ one last-stage psum broadcast)",
            axes=("pp",),
            rules=(
                _asharding.CollectiveRule("collective_permute",
                                          axis="pp", min_count=1),
                _asharding.CollectiveRule("all_reduce", axis="pp",
                                          min_count=1),
            ),
            declared=(),
            state_axis="pp"))
except Exception:                        # pragma: no cover - defensive
    pass
