"""Pallas TPU kernel layer: hand-written kernels for the fusion gaps
XLA's automatic fuser cannot close (arXiv:2301.13062 measured them; the
census in analysis/fusion.py ranks them per program).

Members (each joins the flash-attention kernels in ops/attention.py):

- :mod:`.rnn_scan` — time-fused LSTM/GRU/vanilla-RNN recurrence: the
  hidden-to-hidden matmul, gate nonlinearities and carry update of a
  whole timestep block live in ONE kernel with h/c pinned in VMEM,
  killing the per-step HBM round-trips of the XLA ``while`` loop.
- :mod:`.opt_update` — fused elementwise optimizer update (SGD-mom,
  Adam) over the ZeRO flat padded 1/N shards of gluon/fused_step.py.
- :mod:`.norm` — LayerNorm and bias-GELU forward+backward kernels for
  the transformer/BERT leg.
- :mod:`.moe_rows` — the row movers around the dropless expert layer's
  grouped products (ops/moe.py), whose work follows the pairs a layer
  holds and not the static length of its sorted list; its
  ``scatter_sum`` is also an embedding table's gradient (ops/nn.py
  ``embedding``, the gate's ``embedding_grad``).
- :mod:`.grouped_dot` — that layer's grouped products themselves (rows
  x a group's matrix, the same against the transposed matrix, and the
  per-group rows^T x rows of the matrices' gradient), whose grid is as
  long as the groups; ``lax.ragged_dot`` is their XLA tier.
- :mod:`.ssd_scan` — Mamba-2's chunked selective scan (ops/ssm.py),
  forward and backward: a chunk's (Q x Q) decay and score matrices live
  in VMEM and the state of a group's heads rides the grid's chunk axis,
  where XLA writes those matrices to HBM several times a pass and scans
  the chunk states in a ``while`` loop.

Dispatch discipline (shared by every kernel in this package, and by
``ops.attention.flash_attention``): one ``MXNET_PALLAS`` gate with
three tiers —

- ``auto`` (default): compiled Pallas kernels on TPU backends (in
  single-device programs and ``shard_map`` bodies — a program GSPMD
  partitions over a mesh gets the XLA reference, see
  :func:`_gspmd_reason`), the XLA reference implementation everywhere
  else;
- ``on``: Pallas on TPU; on non-TPU backends the kernels run in
  ``pl.pallas_call(interpret=True)`` mode — the kernel BODY executes
  (as plain XLA ops), which is how tier-1 CPU tests exercise kernels
  and how the parity sweep pins kernel-vs-reference equivalence;
- ``off``: XLA reference everywhere (including TPU) — the A/B switch
  for attribution and the escape hatch for a miscompiling kernel.

Every decision is recorded (``decisions()``, ``tools/diagnose.py
--kernels``; the benchmark and ``chip_smoke.py`` log it beside their
numbers) and counted (``mx_kernel_dispatch_total{path}``).
"""
from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

__all__ = ["pallas_mode", "dispatch", "decisions", "KERNELS",
           "count_traced", "SUPPORTED_DEVICE_KINDS",
           "VMEM_TILE_BUDGET_BYTES",
           "VMEM_BYTES_PER_CORE", "VMEM_SCOPED_DEFAULT_BYTES",
           "vmem_tile_budget"]

#: ``jax.Device.device_kind`` values this kernel layer is sized for
#: (a v5e reports "TPU v5 lite"). The VMEM figures below are this
#: chip's; chip_smoke.py refuses a kind that is not listed.
SUPPORTED_DEVICE_KINDS = ("TPU v5 lite", "TPU v5e")
#: Physical VMEM of one v5e TensorCore
#: (jax.experimental.pallas.tpu.get_tpu_info). A kernel reaches past the
#: scoped default below only by passing ``vmem_limit_bytes``; rnn_scan
#: and the flash kernels do, counted from what they keep resident.
VMEM_BYTES_PER_CORE = 128 * 1024 * 1024
#: What Mosaic grants a kernel that passes no ``vmem_limit_bytes``.
VMEM_SCOPED_DEFAULT_BYTES = 16 * 1024 * 1024
#: VMEM one kernel's CONCURRENT working-set tiles may claim — the budget
#: ops.attention._head_group sizes head groups against, and the one
#: rnn_scan sizes its timestep block against. 4 MiB of the 16 MiB scoped
#: default leaves room for Mosaic's own double buffering of the streamed
#: operands. The DEFAULT: every kernel reads the live value through
#: :func:`vmem_tile_budget` (env overridable), never this
#: constant directly.
VMEM_TILE_BUDGET_BYTES = 4 * 1024 * 1024


def vmem_tile_budget() -> int:
    """THE tile-budget accessor — rnn_scan's timestep-block sizer,
    attention's ``_head_group``, and the norm/opt_update row-block caps
    all size against this one number: ``MXNET_VMEM_TILE_BUDGET`` (bytes)
    when set and parseable, else the default, clamped to
    [64 KiB, the scoped default]."""
    try:
        v = int(float(os.environ["MXNET_VMEM_TILE_BUDGET"]))
    except (KeyError, ValueError, OverflowError):
        v = VMEM_TILE_BUDGET_BYTES
    return max(64 * 1024, min(v, VMEM_SCOPED_DEFAULT_BYTES))


#: the kernel names the dispatch gate knows (diagnose/chip_smoke vocabulary)
KERNELS = ("rnn_scan", "rnn_decode_step", "opt_update", "layernorm",
           "bias_gelu", "flash_attention", "moe_rows", "grouped_dot",
           "ssd_scan", "embedding_grad")

# last decision per kernel name: {kernel: (path, reason)}
_DECISIONS: Dict[str, Tuple[str, str]] = {}


def pallas_mode() -> str:
    """Normalized ``MXNET_PALLAS`` setting: 'auto' | 'on' | 'off'."""
    v = os.environ.get("MXNET_PALLAS", "auto").strip().lower()
    if v in ("", "auto", "default"):
        return "auto"
    if v in ("1", "on", "true", "yes", "force"):
        return "on"
    if v in ("0", "off", "false", "no"):
        return "off"
    return "auto"


def _gspmd_reason() -> Optional[str]:
    """Why a compiled kernel cannot be used in the trace under way, or
    None. Mosaic lowers only into a single-device program or a
    ``shard_map`` body; in a program GSPMD partitions it raises "Mosaic
    kernels cannot be automatically partitioned. Please wrap the call in
    a shard_map" (jax 0.9.0, four v5e chips, PR 21). That program is the
    one traced while a multi-device mesh is active (``with
    make_mesh(...)`` — ``compile_step`` traces there and keeps the mesh
    active for its retraces) outside any manual axis."""
    import jax
    from ...parallel.mesh import current_mesh
    mesh = current_mesh()
    if mesh is None or mesh.size < 2 or \
            jax.sharding.get_abstract_mesh().manual_axes:
        return None
    return (f"GSPMD-partitioned program over {mesh.size} devices: Mosaic "
            "kernels are not partitioned automatically (no shard_map "
            "around the call)")


def _tpu_path(mode: str) -> Tuple[str, str]:
    why = _gspmd_reason()
    return ("xla", why) if why else ("pallas", f"MXNET_PALLAS={mode} on tpu")


def dispatch(kernel: str, supported: bool = True,
             reason: Optional[str] = None,
             detail: Optional[str] = None) -> Tuple[str, str]:
    """The three-tier dispatch decision for one kernel call site.

    Returns ``(path, reason)`` with path one of ``'pallas'`` (compiled
    TPU kernel), ``'interpret'`` (kernel body under
    ``pallas_call(interpret=True)``), ``'xla'`` (reference
    implementation). ``supported=False`` forces the XLA tier with the
    caller's ``reason`` (shape/mode the kernel does not cover) — the
    fallback is automatic, never an error. ``detail`` is what the caller
    can say of a call the kernel takes (flash attention: the layout its
    shapes gave it); it joins the recorded reason."""
    import jax
    mode = pallas_mode()
    if not supported:
        out = ("xla", reason or "kernel does not cover this case")
    elif mode == "off":
        out = ("xla", "MXNET_PALLAS=off")
    else:
        backend = jax.default_backend()
        if backend == "tpu":
            out = _tpu_path(mode)
        elif mode == "on":
            out = ("interpret",
                   f"MXNET_PALLAS=on, non-TPU backend ({backend}): "
                   "kernel body in interpret mode")
        else:
            out = ("xla", f"MXNET_PALLAS=auto, non-TPU backend "
                          f"({backend}): XLA reference")
    if detail and out[0] != "xla":
        out = (out[0], f"{out[1]}; {detail}")
    _DECISIONS[kernel] = out
    count_traced("KERNEL_DISPATCH", "path", out[0])
    return out


def count_traced(metric: str, label_key: Optional[str] = None,
                 label: Optional[str] = None, n: int = 1) -> None:
    """``n`` more (one, unless said) under ``label`` of the labelled
    counter ``telemetry.names.<metric>`` (or of one without labels): what
    the op layer counts while a call is traced (dispatch path, flash
    layout and grid steps, attention mask and form, expert dispatch,
    router rule, row movers and grouped products, MTP modules, the
    selective scan's tier and chunks). Telemetry must never fail a
    kernel call."""
    try:
        from ...telemetry import names as tn
        from ...telemetry import registry as treg
        treg().counter(getattr(tn, metric),
                       label_key=label_key).inc(n, label=label)
    except Exception:
        pass


def decisions() -> Dict[str, Tuple[str, str]]:
    """Last dispatch decision per kernel: {name: (path, reason)}."""
    return dict(_DECISIONS)
