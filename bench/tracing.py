"""Profiler trace: capture, extraction and reduction to numbers.

``extract`` reads the newest ``.xplane.pb`` under a trace directory with
``jax.profiler.ProfileData`` into plain lists of ``(name, start_ns,
duration_ns)``: the device operations per chip and the host spans.
``reduce`` turns those lists into the numbers the benchmark reports:

- ``busy_s``: union of the device-operation intervals inside the traced
  window, averaged over the chips;
- ``idle_share``: 1 - busy / window, averaged over the chips;
- ``collective_share``: time of collective operations / busy time, per
  chip, averaged;
- ``device_ops``: the operations with the most self time (time not
  covered by an operation nested inside them), summed over chips;
- ``idle_gaps``: the longest gaps between device operations on the first
  chip, each named by the shortest host span that covers it.

A trace the profiler cut short reads as an idle tail: the device kept
working after its last recorded operation.  ``reduce`` then returns no
numbers, and says why under ``truncated``: when the device events reach
:data:`EVENT_LIMIT`, or when a chip's last operation ends more than
:data:`TAIL_SHARE` of the window before the window's end.

On a TPU the device operations are the events of each ``/device:TPU:n``
plane's ``XLA Ops`` and ``Async XLA Ops`` lines.  On the CPU backend, which has no device plane,
they are the host events that carry an ``hlo_op`` stat: this is how the
recorded CPU fixture of the tests exercises the same reduction.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re

#: names of collective operations: XLA's, and those that ``shard_map``'s
#: ``psum``/``pmin``/``pmax`` keep on the TPU (``%psum_invariant.17``,
#: ``%pmin.17`` in the channel-sharded loop)
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"psum|pmin|pmax|send|recv", re.IGNORECASE)
TOP = 10
#: device events at which the TPU profiler stops recording (6 x 2**20;
#: full-length scalar loops stopped there on a TPU v5e), less 5%
EVENT_LIMIT = int(6 * 2**20 * 0.95)
#: a window whose device work stops this early is taken as truncated
TAIL_SHARE = 0.5


@contextlib.contextmanager
def capture(trace_dir: str):
    import jax
    jax.profiler.start_trace(trace_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def extract(trace_dir: str) -> dict:
    """``{"devices": {plane: [(name, start_ns, dur_ns), ...]}, "host":
    [...]}`` from the newest trace file under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    devices, host, cpu_ops = {}, [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name in ("XLA Ops", "Async XLA Ops"):
                    devices.setdefault(plane.name, []).extend(
                        (_op_name(e.name), float(e.start_ns),
                         float(e.duration_ns)) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    ev = (e.name, float(e.start_ns), float(e.duration_ns))
                    if any(k == "hlo_op" for k, _ in e.stats):
                        cpu_ops.append(ev)
                    else:
                        host.append(ev)
    if not devices and cpu_ops:
        devices["/host:CPU"] = cpu_ops
    return {"devices": devices, "host": host}


def _op_name(name: str) -> str:
    """``%fusion.242`` of ``%fusion.242 = pred[6144]{...} fusion(...)``:
    TPU traces name an operation by its whole HLO instruction."""
    return name.split(" = ", 1)[0]


def _union(intervals):
    """Merged, sorted ``[start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(events, lo, hi):
    return [(n, max(s, lo), min(s + d, hi)) for n, s, d in events
            if s < hi and s + d > lo]


def _self_times(events) -> dict:
    """Self time per name: each event's duration less what the events
    nested inside it cover (events of one line nest properly)."""
    out: dict = {}
    stack: list = []            # [name, start, end, time of children]
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            _close(stack, out)
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0.0])
    while stack:
        _close(stack, out)
    return out


def _close(stack, out):
    name, s, e, child = stack.pop()
    out[name] = out.get(name, 0.0) + max(e - s - child, 0.0)


def reduce(events: dict, window_ns: tuple) -> dict:
    """Numbers of one traced window ``(start_ns, end_ns)``; all times in
    seconds.  Returns ``None`` values where the trace holds no device
    operation in the window."""
    lo, hi = window_ns
    win = hi - lo
    cut = truncation(events, window_ns)
    if cut:
        return dict(_nothing(win), truncated=cut)
    per_chip = []
    self_time: dict = {}
    first_busy = None
    for plane in sorted(events["devices"]):
        evs = _clip(events["devices"][plane], lo, hi)
        busy = _union([(s, e) for _, s, e in evs])
        busy_ns = sum(e - s for s, e in busy)
        coll = _union([(s, e) for n, s, e in evs if COLLECTIVE.search(n)])
        coll_ns = sum(e - s for s, e in coll)
        per_chip.append((busy_ns, coll_ns))
        for n, t in _self_times(evs).items():
            self_time[n] = self_time.get(n, 0.0) + t
        if first_busy is None and busy:
            first_busy = busy
    if not any(b for b, _ in per_chip) or win <= 0:
        return _nothing(win)
    busy_ns = sum(b for b, _ in per_chip) / len(per_chip)
    coll = [c / b for b, c in per_chip if b > 0]
    ops = sorted(self_time.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": busy_ns * 1e-9,
        "window_s": win * 1e-9,
        "idle_share": 1.0 - busy_ns / win,
        "collective_share": sum(coll) / len(coll) if coll else None,
        "device_ops": [[n, t * 1e-9] for n, t in ops],
        "idle_gaps": _gaps(first_busy, events["host"], lo, hi),
        "truncated": None,
    }


def _nothing(win) -> dict:
    return {"busy_s": None, "window_s": win * 1e-9, "idle_share": None,
            "collective_share": None, "device_ops": [], "idle_gaps": [],
            "truncated": None}


def truncation(events: dict, window_ns: tuple) -> str | None:
    """Why the trace looks cut short inside ``window_ns``, or None."""
    n = sum(len(v) for v in events["devices"].values())
    if n >= EVENT_LIMIT:
        return f"{n} device events, at the profiler's limit"
    lo, hi = window_ns
    for plane, evs in sorted(events["devices"].items()):
        ends = [s + d for _, s, d in evs if s < hi and s + d > lo]
        if ends and hi - max(ends) > TAIL_SHARE * (hi - lo):
            return (f"{plane}: last operation ends "
                    f"{(hi - max(ends)) * 1e-9:.6f} s before the window's end")
    return None


def _gaps(busy, host, lo, hi) -> list:
    """The longest idle gaps inside ``[lo, hi)``, each named by the
    shortest host span that covers its middle."""
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:TOP]:
        mid = (s + e) / 2
        cover = [(d, n) for n, hs, d in host if hs <= mid < hs + d]
        label = min(cover)[1] if cover else "no host span"
        where = ("before the first device op" if s == lo else
                 "after the last device op" if e == hi else "between ops")
        out.append([f"{label} ({where})", (e - s) * 1e-9])
    return out
