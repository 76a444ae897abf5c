"""xplane -> window_s, busy_s, per-name device time, top operations.

What a real trace of this chip and jax version carries (TPU v5 lite, jax
0.9.0, looked at by hand in PR 24 through ``run.py --keep-trace``):

- PLANES: ``/device:TPU:0`` (one per chip), ``/host:CPU``, and five that
  hold no line here: ``#Chip0 Host Interface``, ``#Chip0 Misc``,
  ``/host:metadata``, ``/device:CUSTOM:Megascale Trace``, ``Task
  Environment``.
- LINES of the device plane: ``Steps`` and ``XLA Modules`` (3 events a
  training step: the step's ``jit_fused(<fingerprint>)`` and the two small
  programs of its RNG key split, ``_threefry_split`` and ``_unstack``),
  ``XLA Ops`` (READ
  HERE), ``Async XLA Ops`` (copy-start/done and slice-start/done spans,
  which overlap ``XLA Ops``), ``TC Overlay`` and ``Scalar Unit`` (empty).
- LINES of the host plane: one per thread. ``python3`` holds the Python
  tracer's frames (``$engine.py:219 push``) AND the ``TraceAnnotation``s
  (``grid_window``, ``mx_train_step``); ``main/<tid>``,
  ``pjrt-tpu-tasks/<tid>``, ``tfrt-non-blocking-queue/<tid>`` and others
  hold the runtime's.
- AN EVENT of ``XLA Ops`` is named by its whole HLO instruction
  (``%fusion.108 = (f32[33278,650]{...}) fusion(...), kind=kOutput,
  calls=%fused_computation.208``) and carries three stats:
  ``device_offset_ps``, ``device_duration_ps``, ``Time Scale
  Multiplier``. No ``op_name``, no scope: ``jax.named_scope`` does NOT
  reach the device line. It is in the ``metadata={op_name=...}`` of the
  compiled HLO text, which names the same instructions: hence ``Hlo``.
- FLASH ATTENTION: two Pallas custom calls a layer,
  ``%jvp_flash_attention_.N`` (0.79 ms) and
  ``%transpose_jvp_flash_attention__.N`` (0.86 ms), on ``bf16[384,512,
  128]``: the head's 64 padded to 128; ``op_name``
  ``jit(fused)/jvp(flash_attention)/pallas_call`` and
  ``.../transpose(jvp(flash_attention))/pallas_call``, with the pad, slice
  and broadcast fusions around them under the same scope.
- THE LSTM: ``rnn_scan`` declines at 650 units ("w_hh (4x768x768) with its
  dW accumulators and one timestep of tiles exceed VMEM"), so ``rnn_lstm``
  runs as XLA ``while`` loops (``%while.75``), whose event ENCLOSES its
  body's fusions on the same line: 3.152 s of durations in a 2.754 s
  window whose union is 2.747 s. The BERT step has no loop: sum = union.

Rules of the reduction (each pinned by tests/benchmark_grid/test_grid.py):

- ONE device plane per chip (``/device:TPU:<n>``) and ONE line of it (the
  operations line, ``XLA Ops``). Other lines of the plane (steps, modules,
  async spans) repeat the same time under other names; reading two of
  them counts every second twice.
- The window is a span on the trace's own clock: the ``grid_window`` host
  annotation that ``run.py`` puts around whole steps closed by a
  completion barrier. Every event is clipped to it.
- ``busy_s`` is the UNION of the clipped intervals, never a sum: on a
  device line a ``while`` loop's event encloses its body's events, so a
  sum reads above the window in exactly the programs that loop.
- Per-name time is SELF time: an event that encloses others on its line
  gives its duration less what its children cover, so a loop and its body
  are never both counted.
- An event's scope is the ``op_name`` its instruction has in the optimized
  HLO text of the step's own program, and only for events inside that
  module's spans on ``XLA Modules``: another program's ``fusion.16`` is
  not this one's. A text that does not name what ran is an error.

In a ``--rehearse`` run on the CPU there is no device plane: the events
that carry an ``hlo_op`` stat on the host plane's executor threads stand in
so that the whole path runs in the tests. Nothing read there is a device
number and ``run.py`` never prints it under a device's name.
"""
import bisect
import glob
import os
import re
from typing import Iterable, NamedTuple, Optional

WINDOW_ANNOTATION = "grid_window"
DEVICE_PLANE_PREFIX = "/device:TPU:"
DEVICE_OPS_LINE = "XLA Ops"
DEVICE_MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


class Event(NamedTuple):
    start: float      # seconds on the trace's clock
    end: float
    name: str         # the HLO instruction's own name first, then its kind
    scope: str = ""   # the op's scope path (``op_name``), from ``Hlo``
    module: str = ""  # the XLA module the event ran in, where known


class Hlo(NamedTuple):
    """What the optimized HLO text of the step's program says of its
    instructions: the module's name, ``{instruction: op_name}`` and the
    names of all its instructions."""
    module: str
    scopes: dict
    names: frozenset


def union_seconds(intervals: Iterable, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    clipped = sorted((max(s, lo), min(e, hi)) for s, e in intervals
                     if min(e, hi) > max(s, lo))
    total, cur_s, cur_e = 0.0, None, None
    for s, e in clipped:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def self_times(events: Iterable[Event], lo: float, hi: float) -> list:
    """``[(event, self_seconds)]`` for the events of ONE line, clipped to
    the window: an event's duration less the union of the events it
    encloses, so that enclosing and enclosed time is counted once."""
    evs = sorted((e for e in events if min(e.end, hi) > max(e.start, lo)),
                 key=lambda e: (e.start, -e.end))
    out, stack = [], []     # stack of [event, list of child intervals]

    def close(entry):
        ev, kids = entry
        s, e = max(ev.start, lo), min(ev.end, hi)
        out.append((ev, max(0.0, (e - s) - union_seconds(kids, s, e))))

    for ev in evs:
        while stack and ev.start >= stack[-1][0].end:
            close(stack.pop())
        if stack:
            stack[-1][1].append((ev.start, ev.end))
            if not ev.scope:    # a loop's body under the loop's scope
                ev = ev._replace(scope=stack[-1][0].scope)
        stack.append([ev, []])
    while stack:
        close(stack.pop())
    return out


def reduce_lines(lines: list, window: tuple, top: int = 10) -> dict:
    """``lines``: one list of Events per chip (its operations line).
    ``window``: ``(lo, hi)`` on the same clock. Busy time is averaged
    over the chips; per-name and per-scope self time is summed over
    them."""
    lo, hi = window
    if not hi > lo:
        raise ValueError(f"empty window {window}")
    if not lines:
        raise ValueError("no device line to read")
    busy = [union_seconds(((e.start, e.end) for e in line), lo, hi)
            for line in lines]
    by_name, by_op, leaf = {}, {}, []
    for line in lines:
        for ev, t in self_times(line, lo, hi):
            if t > 0:
                by_name[ev.name] = by_name.get(ev.name, 0.0) + t
                # the op the program named, where the HLO text gave one:
                # twelve layers' kernels under one name, not twelve
                op = ev.scope or ev.name
                by_op[op] = by_op.get(op, 0.0) + t
                leaf.append((ev, t))
    ranked = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": hi - lo,
            "busy_s": sum(busy) / len(busy),
            "by_name": by_name,
            "leaf": leaf,
            "device_ops": [[n[:160], t] for n, t in ranked]}


def scope_seconds(reduced: dict, scope: str) -> Optional[float]:
    """Summed self time of the events whose scope path has ``scope`` as
    one of its ``/``-separated parts (``transpose(jvp(flash_attention))``
    counts: the backward pass carries the forward's scope inside it).
    None where no event carries it."""
    total, found = 0.0, False
    for ev, t in reduced["leaf"]:
        if _has_part(ev.scope, scope):
            total += t
            found = True
    return total if found else None


def _has_part(path: str, scope: str) -> bool:
    for part in path.split("/"):
        core = part
        while "(" in core and core.endswith(")"):
            core = core[core.index("(") + 1:-1]
        if core == scope:
            return True
    return False


def idle_gaps(line: list, window: tuple, host: list = (),
              top: int = 10) -> list:
    """The idle time of one device line inside the window by what the host
    was doing, longest first: ``[[name, seconds]]``. A gap is named by the
    innermost event, at the gap's middle, of the host thread that holds
    the window's annotation (``host``, on the trace's own clock); where
    the host recorded nothing there, by the operation the device ran
    next. Gaps of one name are summed."""
    lo, hi = window
    evs = sorted((e for e in line if min(e.end, hi) > max(e.start, lo)),
                 key=lambda e: e.start)
    gaps, edge = [], lo
    for e in evs:
        if e.start > edge:
            gaps.append((edge, e.start, f"before {e.name}"))
        edge = max(edge, e.end)
    if hi > edge:
        gaps.append((edge, hi, "before the window's end"))
    # one sweep: the thread's events nest as its call stack does, so the
    # innermost one open at a moment is the top of the stack of those begun
    total, stack, i = {}, [], 0
    for s, e, fallback in gaps:             # in time order, as ``host`` is
        mid = (s + e) / 2
        while i < len(host) and host[i].start <= mid:
            stack.append(host[i])
            i += 1
        while stack and stack[-1].end <= mid:
            stack.pop()
        name = f"host: {stack[-1].name}" if stack else fallback
        total[name] = total.get(name, 0.0) + e - s
    return [[n, t] for n, t in
            sorted(total.items(), key=lambda kv: -kv[1])[:top]]


# ---------------------------------------------------------------------------
# the program's optimized HLO: which scope an instruction belongs to
# ---------------------------------------------------------------------------

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def parse_hlo(text: str) -> Hlo:
    """``compiled.as_text()`` -> the module's name and each instruction's
    ``op_name`` (the ``jax.named_scope`` path the program gave the op).
    A fusion that carries none takes its fused computation's: the root's,
    else the one most of its instructions carry."""
    module, own, calls, inside, roots, names = "", {}, {}, {}, {}, set()
    computation = None
    for raw in text.splitlines():
        if raw.startswith("HloModule "):
            module = raw.split()[1].rstrip(",")
            continue
        m = _INSTRUCTION.match(raw)
        if m is None:
            c = _COMPUTATION.match(raw)
            if c is not None:
                computation = c.group(1)
            continue
        name = m.group(2)
        names.add(name)
        op = _OP_NAME.search(raw)
        if op is not None:
            own[name] = op.group(1)
            inside.setdefault(computation, []).append(op.group(1))
            if m.group(1):
                roots[computation] = op.group(1)
        called = _CALLS.search(raw)
        if called is not None:
            calls[name] = called.group(1)
    scopes = dict(own)
    for name, computation in calls.items():
        if name in scopes:
            continue
        ops = inside.get(computation)
        if computation in roots:
            scopes[name] = roots[computation]
        elif ops:
            scopes[name] = max(set(ops), key=ops.count)
    return Hlo(module, scopes, frozenset(names))


# ---------------------------------------------------------------------------
# reading the profiler's file
# ---------------------------------------------------------------------------

def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


_LAYOUT = re.compile(r"\{[^{}]*\}")
_HLO = re.compile(r"^(\(.*?\)|\S+) ([\w\-]+)\(")


def short_name(name: str) -> str:
    """``%fusion.3 = f32[8,128]{1,0:T(8,128)} fusion(...)``, the whole HLO
    instruction the device line names an event by, cut to
    ``fusion.3 fusion f32[8,128]``."""
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    m = _HLO.match(_LAYOUT.sub("", rhs))
    lhs = lhs.lstrip("%")
    return f"{lhs} {m.group(2)} {m.group(1)}"[:96] if m else lhs


def _span(e) -> tuple:
    start = e.start_ns * 1e-9
    return start, start + e.duration_ns * 1e-9


def _module_of(spans: list, t: float) -> str:
    """The module whose span on the ``XLA Modules`` line holds ``t``."""
    i = bisect.bisect_right(spans, (t, "￿")) - 1
    return spans[i][2] if i >= 0 and t < spans[i][1] else ""


def _scoped(ev: Event, hlo: Optional[Hlo]) -> Event:
    if hlo is None or ev.module != hlo.module:
        return ev
    return ev._replace(scope=hlo.scopes.get(ev.name.split(" ", 1)[0], ""))


def read(path: str, hlo: Optional[Hlo] = None,
         rehearse: bool = False) -> dict:
    """``{"lines": [events per chip], "window": (lo, hi) or None, "host":
    [events of the thread that holds the window's annotation], "planes":
    {plane: {line: n_events}}}`` from one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    lines, window, host, planes = [], None, [], {}
    for plane in data.planes:
        census = planes.setdefault(plane.name, {})
        for line in plane.lines:
            census[line.name] = census.get(line.name, 0) \
                + sum(1 for _ in line.events)
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            by_name = {}
            for ln in plane.lines:
                by_name.setdefault(ln.name, []).append(ln)
            ops = by_name.get(DEVICE_OPS_LINE, [])
            if len(ops) != 1:
                raise ValueError(
                    f"{plane.name}: {len(ops)} lines named "
                    f"{DEVICE_OPS_LINE!r} among {sorted(by_name)}")
            spans = sorted(_span(e) + (e.name.split("(", 1)[0],)
                           for ln in by_name.get(DEVICE_MODULES_LINE, [])
                           for e in ln.events)
            lines.append([
                _scoped(Event(*_span(e), short_name(e.name), "",
                              _module_of(spans, _span(e)[0])), hlo)
                for e in ops[0].events])
        elif plane.name == HOST_PLANE:
            host_ops = []
            for line in plane.lines:
                evs = list(line.events)
                marks = [e for e in evs if e.name == WINDOW_ANNOTATION]
                if marks:
                    window = _span(marks[-1])
                    host = sorted(Event(*_span(e), e.name[:96]) for e in evs
                                  if e.name != WINDOW_ANNOTATION)
                if rehearse:
                    for e in evs:
                        stats = {k: v for k, v in e.stats}
                        if "hlo_op" in stats:
                            host_ops.append(_scoped(Event(
                                *_span(e), str(stats["hlo_op"]), "",
                                str(stats.get("hlo_module", ""))), hlo))
            if rehearse and host_ops:
                lines.append(host_ops)
    return {"lines": lines, "window": window, "host": host,
            "planes": planes}


def reduce_trace(trace_dir: str, host_window_s: float,
                 hlo_text: Optional[str] = None,
                 rehearse: bool = False) -> dict:
    """The whole reduction of the newest trace under ``trace_dir``.

    ``host_window_s`` is the wall time of the traced window by the host's
    clock. It IS ``window_s``. The ``grid_window`` annotation only places
    that span on the trace's clock; where the trace lacks it the span is
    laid to end at the last device event (the window ends in a completion
    barrier). ``hlo_text`` is the optimized HLO of the step's program,
    from which each event takes its scope."""
    hlo = parse_hlo(hlo_text) if hlo_text else None
    raw = read(newest_xplane(trace_dir), hlo, rehearse=rehearse)
    if not raw["lines"] or not any(raw["lines"]):
        raise ValueError("the trace holds no device operation: planes "
                         f"{ {p: sorted(l) for p, l in raw['planes'].items()} }")
    if raw["window"] is not None:
        lo = raw["window"][0]
    else:
        lo = max(e.end for line in raw["lines"] for e in line) \
            - host_window_s
    window = (lo, lo + host_window_s)
    out = reduce_lines(raw["lines"], window)
    # the union is clipped to a span of exactly this length; only the
    # round-off of ``lo + length - lo`` can put it a hair above
    out["window_s"] = host_window_s
    out["busy_s"] = min(out["busy_s"], host_window_s)
    out["planes"] = raw["planes"]
    out["window_from"] = "annotation" if raw["window"] else "last_event"
    out["hlo_module"] = hlo.module if hlo else ""
    total = max(sum(t for _, t in out["leaf"]), 1e-30)
    out["scoped_share"] = sum(t for ev, t in out["leaf"] if ev.scope) / total
    if hlo is not None:
        # the text has to be of the program the trace saw: its module ran,
        # and what ran in it is named there
        mine = [(ev, t) for ev, t in out["leaf"] if ev.module == hlo.module]
        known = sum(t for ev, t in mine
                    if ev.name.split(" ", 1)[0] in hlo.names)
        if not mine or known < 0.99 * sum(t for _, t in mine):
            raise ValueError(
                f"the HLO text of module {hlo.module!r} names "
                f"{known:.4f}s of the {sum(t for _, t in mine):.4f}s its "
                f"{len(mine)} events took: not the program that was traced")
    out["idle_gaps"] = idle_gaps(raw["lines"][0], window, raw["host"])
    return out


def roofline_share(ctx: dict, scope: str) -> Optional[float]:
    """For the ``<kernel>_roofline`` readers: the least time the chip could
    take for the scope's FLOPs and bytes of the traced steps (the larger of
    the two bounds, from the configuration's ``kernel_costs``) over the
    device time of the events under that scope, in percent. None where the
    trace carries no such event: never 0."""
    costs = ctx["model"].kernel_costs(ctx["cfg"], ctx["traffic"]).get(scope)
    if costs is None or not ctx["trace"]:
        return None
    seconds = scope_seconds(ctx["trace"], scope)
    if not seconds:
        return None
    peaks = ctx["peaks"]
    least = max(costs["flops"] / peaks["bf16_flops_per_s"],
                costs["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx["traced"]["steps"] * ctx["chips"] / seconds
