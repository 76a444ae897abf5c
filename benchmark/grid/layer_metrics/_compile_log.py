"""The program's compile log (``mxnet_tpu.runtime.compile_log()``) cut to
set-up, for the readers ``setup_trace_lower_s``, ``setup_compile_s`` and
``setup_programs``: the entries that ended before the window's first span.
The log's stamps and the timeline's are one clock (``perf_counter``).

None where the program keeps no log, where the ring dropped entries (a
part of set-up is then missing: no number beats a low one), or where the
run recorded no span to place the window by."""
import trace_reduce


def before_window(ctx, phases):
    """``[(t0, t1)]`` of the log's entries of ``phases`` that ended in
    set-up, or None."""
    from mxnet_tpu import runtime
    read_log = getattr(runtime, "compile_log", None)
    if read_log is None or not ctx["spans"]:
        return None
    log = read_log()
    if log["dropped"]:
        return None
    window_t0 = min(e["t0"] for e in ctx["spans"])
    return [(e["t0"], e["t1"]) for e in log["events"]
            if e["phase"] in phases and e["t1"] <= window_t0]


def union_before_window(ctx, phases):
    """Seconds covered by those entries: a union, since an inner ``jit``'s
    tracing nests inside its caller's."""
    spans = before_window(ctx, phases)
    if not spans:
        return None
    return trace_reduce.union_seconds(spans, min(s for s, _ in spans),
                                      max(e for _, e in spans))
