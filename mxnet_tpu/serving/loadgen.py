"""Serving load generator: closed- and open-loop traffic with p50/p99.

The two canonical load shapes for latency benchmarking:

- **closed loop** (:func:`run_closed_loop`): C concurrent clients, each
  issuing its next request the moment the previous one completes —
  measures sustainable throughput (QPS) under a fixed concurrency and
  the latency the system settles into at that load.
- **open loop** (:func:`run_open_loop`): requests arrive on a Poisson
  process at a target rate regardless of completions — the honest
  latency distribution under un-coordinated traffic (closed loops hide
  queueing spikes by self-throttling: coordinated omission).

Both record each request's TERMINAL STATE — one of ``ok`` (completed;
within the deadline when one is given), ``rejected`` (shed at
admission: a typed :class:`~mxnet_tpu.serving.Overloaded`),
``deadline_missed`` (a typed :class:`~mxnet_tpu.serving
.DeadlineExceeded`, or a completion that arrived after ``deadline_s``),
or ``error`` (anything else) — and report **goodput** (ok/s) separately
from raw QPS: under overload with shedding armed, goodput is the honest
capacity number; raw QPS flatters a service that answers late.

Reports carry QPS, goodput_qps, reject_rate, deadline_miss_rate, and
exact p50/p99 latency computed from the raw per-request samples of the
``ok`` population (no histogram interpolation;
``mx_serving_request_seconds`` carries the live-histogram view).

Fleet targets: :func:`fleet_issue` / :func:`fleet_submit` adapt a
:class:`~mxnet_tpu.serving.FleetRouter` (or a list of per-replica
submit callables) into the loops' issue/submit shape, carrying the
``fut.replica`` routing breadcrumb through successes AND failures.
When those breadcrumbs are present, both loops add a ``replicas`` key
to the report — per-replica {qps, goodput_qps, p50/p99, outcome
census} next to the fleet aggregate — so a hot or broken replica is
visible in the same artifact as the fleet number.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Optional

import numpy as onp

__all__ = ["run_closed_loop", "run_open_loop", "percentiles",
           "classify_outcome", "streaming_summary", "fleet_issue",
           "fleet_submit"]

OUTCOMES = ("ok", "rejected", "deadline_missed", "error")


def classify_outcome(exc: BaseException) -> str:
    """Map a request failure to its terminal state: a typed
    ``Overloaded`` (anywhere in the cause chain) is ``rejected``, a
    typed ``DeadlineExceeded`` is ``deadline_missed``, anything else
    is ``error``."""
    from .resilience import DeadlineExceeded, Overloaded
    seen = set()
    e: Optional[BaseException] = exc
    while e is not None and id(e) not in seen:
        seen.add(id(e))
        if isinstance(e, Overloaded):
            return "rejected"
        if isinstance(e, DeadlineExceeded):
            return "deadline_missed"
        e = e.__cause__ or e.__context__
    return "error"


def percentiles(latencies) -> dict:
    """{p50_ms, p99_ms, mean_ms} from raw per-request seconds."""
    if not len(latencies):
        return {"p50_ms": None, "p99_ms": None, "mean_ms": None}
    a = onp.asarray(latencies, dtype="float64") * 1e3
    return {"p50_ms": round(float(onp.percentile(a, 50)), 3),
            "p99_ms": round(float(onp.percentile(a, 99)), 3),
            "mean_ms": round(float(a.mean()), 3)}


def streaming_summary(records, wall: Optional[float] = None) -> dict:
    """Aggregate per-request STREAMING records into the token-level
    latency view request-level p50/p99 cannot express: exact TTFT
    (time to first token) and TPOT (time per output token)
    percentiles, plus token goodput. A record is a dict with
    ``ttft_s`` (float), ``tpot_s`` (inter-token gaps, seconds) and
    ``tokens`` — the shape ``DecodeStream.record()`` produces."""
    records = [r for r in records if isinstance(r, dict)]
    ttfts = [r["ttft_s"] for r in records
             if r.get("ttft_s") is not None]
    tpots = [g for r in records for g in (r.get("tpot_s") or ())]
    tokens = sum(int(r.get("tokens") or 0) for r in records)
    out = {}
    out.update({"ttft_" + k: v for k, v in percentiles(ttfts).items()})
    out.update({"tpot_" + k: v for k, v in percentiles(tpots).items()})
    out["stream_tokens"] = tokens
    out["tokens_per_sec"] = round(tokens / wall, 2) \
        if wall and wall > 0 else None
    # speculative-decode view (present only when records carry the
    # engine's per-step accounting): acceptance_rate = accepted drafts
    # / proposed drafts, and tokens_per_step percentiles over the
    # pooled per-step emitted-token counts (> 1 means a verify step
    # emitted a whole accepted block in one dispatch)
    steps = [n for r in records for n in (r.get("step_tokens") or ())]
    if steps:
        drafted = sum(int(r.get("spec_drafted") or 0) for r in records)
        accepted = sum(int(r.get("spec_accepted") or 0)
                       for r in records)
        a = onp.asarray(steps, dtype="float64")
        out["acceptance_rate"] = round(accepted / drafted, 4) \
            if drafted else None
        out["tokens_per_step"] = {
            "mean": round(float(a.mean()), 3),
            "p50": round(float(onp.percentile(a, 50)), 3),
            "p99": round(float(onp.percentile(a, 99)), 3),
            "max": int(a.max()),
        }
    return out


def _maybe_streaming(out: dict, records: list, wall: float) -> dict:
    """Attach TTFT/TPOT/goodput next to the request-level percentiles
    when the issue/wait callables returned streaming records (a dict
    carrying ``ttft_s``); plain predictors change nothing."""
    recs = [r for r in records
            if isinstance(r, dict) and "ttft_s" in r]
    if recs:
        out.update(streaming_summary(recs, wall))
    return out


def _tally_replica(by: dict, replica, outcome: str, dt):
    """Fold one terminal state into the per-replica census (no-op when
    the request carried no routing breadcrumb — plain predictors)."""
    if not replica:
        return
    rec = by.setdefault(replica, {
        "outcomes": {k: 0 for k in OUTCOMES}, "lat": []})
    rec["outcomes"][outcome] += 1
    if dt is not None:
        rec["lat"].append(dt)


def _replica_report(by: dict, wall: float) -> dict:
    out = {}
    for name in sorted(by):
        rec = by[name]
        oc = rec["outcomes"]
        done = oc["ok"] + oc["deadline_missed"] + oc["error"]
        r = {"qps": round(done / wall, 2) if wall > 0 else None,
             "goodput_qps": round(oc["ok"] / wall, 2)
             if wall > 0 else None,
             "outcomes": dict(oc)}
        r.update(percentiles(rec["lat"]))
        out[name] = r
    return out


def _report(mode: str, outcomes: dict, ok_lat, wall: float,
            extra: dict, by_replica: Optional[dict] = None) -> dict:
    total = sum(outcomes.values())
    done = outcomes["ok"] + outcomes["deadline_missed"] \
        + outcomes["error"]
    out = dict(extra)
    out.update({
        "mode": mode,
        "requests": int(outcomes["ok"]),
        "issued": int(total),
        "errors": int(outcomes["error"]),
        "outcomes": dict(outcomes),
        "wall_s": round(wall, 4),
        "qps": round(done / wall, 2) if wall > 0 else None,
        "goodput_qps": round(outcomes["ok"] / wall, 2)
        if wall > 0 else None,
        "reject_rate": round(outcomes["rejected"] / total, 4)
        if total else None,
        "deadline_miss_rate": round(outcomes["deadline_missed"] / total,
                                    4) if total else None,
    })
    out.update(percentiles(ok_lat))
    if by_replica:
        out["replicas"] = _replica_report(by_replica, wall)
    return out


def _submit_of(target) -> Callable:
    """One submit callable from a fleet target: a FleetRouter (or any
    object with ``.submit``) routes every request; a LIST of submit
    callables (one per replica) is round-robined by request index."""
    if callable(getattr(target, "submit", None)):
        return lambda i, *args, **kw: target.submit(*args, **kw)
    fns = list(target)
    if not fns or not all(callable(f) for f in fns):
        raise TypeError(
            "fleet target must be a router (with .submit) or a "
            "non-empty list of submit callables")
    return lambda i, *args, **kw: fns[i % len(fns)](*args, **kw)


def _attributed_wait(fut, timeout):
    """``fut.result`` with the routing breadcrumb carried through both
    outcomes: failures get ``e.replica`` stamped so the loops can
    attribute sheds/deadline-misses, successes return the per-replica
    record."""
    try:
        fut.result(timeout)
    except BaseException as e:
        rep = getattr(fut, "replica", None)
        if rep is not None:
            try:
                e.replica = rep
            except Exception:    # pragma: no cover - exotic exception
                pass
        raise
    return {"replica": getattr(fut, "replica", None)}


def fleet_issue(target, make_args: Callable[[int], tuple],
                deadline_ms: Optional[float] = None,
                timeout: Optional[float] = 30.0) -> Callable:
    """Adapt a fleet target into :func:`run_closed_loop`'s
    ``issue(i)``: submit ``make_args(i)`` through the router (or the
    ``i % N``-th of a list of submit callables), wait for the result,
    and return the per-replica record the loop's census groups by."""
    submit = _submit_of(target)

    def issue(i: int):
        fut = submit(i, *make_args(i), deadline_ms=deadline_ms)
        return _attributed_wait(fut, timeout)
    return issue


def fleet_submit(target, make_args: Callable[[int], tuple],
                 deadline_ms: Optional[float] = None) -> Callable:
    """Adapt a fleet target into :func:`run_open_loop`'s
    ``submit(i)``: enqueue without waiting, return the wait callable
    (which yields the per-replica record)."""
    submit = _submit_of(target)

    def submit_one(i: int):
        fut = submit(i, *make_args(i), deadline_ms=deadline_ms)
        return lambda timeout=None: _attributed_wait(fut, timeout)
    return submit_one


def run_closed_loop(issue: Callable[[int], None], concurrency: int,
                    requests: int,
                    deadline_s: Optional[float] = None) -> dict:
    """C worker threads; each calls ``issue(i)`` (submit AND wait for
    one request) back-to-back until ``requests`` total are issued.
    Latency is the full ``issue`` wall time per request; with
    ``deadline_s`` a completion slower than it counts as
    ``deadline_missed``, not ``ok`` (goodput is ok/s). An ``issue``
    that RETURNS a streaming record (a dict with ``ttft_s``/``tpot_s``
    per token — ``DecodeStream.record()``) additionally gets exact
    TTFT/TPOT percentiles and ``tokens_per_sec`` in the report; one
    that returns/raises with a ``replica`` breadcrumb
    (:func:`fleet_issue`) additionally gets the per-replica census."""
    outcomes = {k: 0 for k in OUTCOMES}
    ok_lat: list = []
    stream_recs: list = []
    by_replica: dict = {}
    # bare on purpose: load-generator harness local; leaf lock
    lock = threading.Lock()  # mx-lint: allow=MXA009
    counter = [0]

    def worker():
        while True:
            with lock:
                i = counter[0]
                if i >= requests:
                    return
                counter[0] += 1
            t0 = time.perf_counter()
            try:
                ret = issue(i)
            except Exception as e:
                with lock:
                    oc = classify_outcome(e)
                    outcomes[oc] += 1
                    _tally_replica(by_replica,
                                   getattr(e, "replica", None), oc, None)
                continue
            dt = time.perf_counter() - t0
            with lock:
                rep = ret.get("replica") if isinstance(ret, dict) \
                    else None
                if isinstance(ret, dict) and "ttft_s" in ret:
                    stream_recs.append(ret)
                if deadline_s is not None and dt > deadline_s:
                    outcomes["deadline_missed"] += 1
                    _tally_replica(by_replica, rep, "deadline_missed",
                                   None)
                else:
                    outcomes["ok"] += 1
                    ok_lat.append(dt)
                    _tally_replica(by_replica, rep, "ok", dt)

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(max(1, concurrency))]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    return _maybe_streaming(
        _report("closed", outcomes, ok_lat, wall,
                {"concurrency": int(concurrency)}, by_replica),
        stream_recs, wall)


def run_open_loop(submit: Callable[[int], Callable[[], None]],
                  rate_qps: float, requests: int,
                  seed: int = 0,
                  timeout: Optional[float] = 120.0,
                  deadline_s: Optional[float] = None) -> dict:
    """Poisson arrivals at ``rate_qps``: ``submit(i)`` must enqueue
    request ``i`` WITHOUT waiting and return a zero-arg wait callable
    (e.g. ``DynamicBatcher.submit(...).result``). Arrival jitter is
    deterministic per ``seed``. Latency = arrival (scheduled submit)
    to completion — queueing included, no coordinated omission. A
    ``submit`` that raises (admission-control shedding) is recorded as
    that request's terminal state — the arrival clock keeps ticking,
    exactly like real un-coordinated traffic."""
    import queue as _queue
    rng = onp.random.RandomState(seed)
    gaps = rng.exponential(1.0 / max(rate_qps, 1e-9), size=requests)
    outcomes = {k: 0 for k in OUTCOMES}
    ok_lat: list = []
    stream_recs: list = []
    by_replica: dict = {}
    # bare on purpose: load-generator harness local; leaf lock
    lock = threading.Lock()  # mx-lint: allow=MXA009
    # a waiter pool records each completion AS IT HAPPENS — waiting
    # sequentially after the arrival phase would inflate every early
    # request's latency by the remaining arrival time
    work: "_queue.Queue" = _queue.Queue()

    def waiter():
        while True:
            item = work.get()
            if item is None:
                return
            t0, wait = item
            try:
                try:
                    ret = wait() if timeout is None else wait(timeout)
                except TypeError:
                    ret = wait()
            except Exception as e:
                with lock:
                    oc = classify_outcome(e)
                    outcomes[oc] += 1
                    _tally_replica(by_replica,
                                   getattr(e, "replica", None), oc, None)
                continue
            dt = time.perf_counter() - t0
            with lock:
                rep = ret.get("replica") if isinstance(ret, dict) \
                    else None
                if isinstance(ret, dict) and "ttft_s" in ret:
                    stream_recs.append(ret)
                if deadline_s is not None and dt > deadline_s:
                    outcomes["deadline_missed"] += 1
                    _tally_replica(by_replica, rep, "deadline_missed",
                                   None)
                else:
                    outcomes["ok"] += 1
                    ok_lat.append(dt)
                    _tally_replica(by_replica, rep, "ok", dt)

    n_waiters = min(32, max(4, requests // 8))
    threads = [threading.Thread(target=waiter, daemon=True)
               for _ in range(n_waiters)]
    for t in threads:
        t.start()
    t_start = time.perf_counter()
    next_t = t_start
    for i in range(requests):
        now = time.perf_counter()
        if next_t > now:
            time.sleep(next_t - now)
        t0 = time.perf_counter()
        try:
            waitfn = submit(i)
        except Exception as e:       # shed at admission
            with lock:
                oc = classify_outcome(e)
                outcomes[oc] += 1
                _tally_replica(by_replica,
                               getattr(e, "replica", None), oc, None)
        else:
            work.put((t0, waitfn))
        next_t += gaps[i]
    for _ in threads:
        work.put(None)
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_start
    return _maybe_streaming(
        _report("open", outcomes, ok_lat, wall,
                {"rate_qps": float(rate_qps)}, by_replica),
        stream_recs, wall)
