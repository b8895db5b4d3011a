"""From a profiler trace to the numbers the per-layer metrics read.

Two steps.  ``load`` reads the ``.xplane.pb`` that ``jax.profiler``
wrote and keeps only what the reduction needs, in a plain dict (which
is also the format of the recorded trace the tests check):

  {"window": [start_ns, end_ns],                 the traced window
   "devices": {plane: [[start_ns, dur_ns, op, module, scope], ...]},
   "modules": {plane: [[start_ns, dur_ns, module], ...]},
   "host": [[start_ns, dur_ns, name], ...]}      the benchmark's spans

``scope`` is the op's ``jax.named_scope`` path (from the trace's own
statistics, or else from the compiled program's HLO metadata).  Then
``reduce`` computes, per device and averaged over devices: the union of
busy intervals inside the window, the idle share, device time by scope
and by op, and the longest idle gaps named by the host span that covers
them.
"""
from __future__ import annotations

import glob
import os
import re
import shutil

_HLO_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?metadata=\{[^}]*?op_name=\"([^\"]*)\"")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
_OP_NAME = re.compile(r"^%?([\w.\-]+)\s*=")


def hlo_scopes(hlo_text: str) -> dict:
    """``{module: {instruction: op_name}}`` from compiled HLO text."""
    out: dict = {}
    current = None
    for line in hlo_text.splitlines():
        m = _MODULE.match(line)
        if m:
            current = out.setdefault(m.group(1), {})
            continue
        m = _HLO_LINE.match(line)
        if m and current is not None:
            current[m.group(1)] = m.group(2)
    return out


def _stats(event) -> dict:
    try:
        return {k: v for k, v in event.stats}
    except Exception:                                   # noqa: BLE001
        return {}


def start(logdir: str) -> None:
    """Start the profiler into an emptied ``logdir``."""
    import jax

    shutil.rmtree(logdir, ignore_errors=True)
    jax.profiler.start_trace(logdir)


def stop_and_load(logdir: str, window_name: str,
                  scopes: dict | None = None) -> dict:
    """Stop the profiler, read its trace (``load``) and delete the files,
    so that runs leave no traces on disk."""
    import jax

    jax.profiler.stop_trace()
    try:
        return load(logdir, window_name, scopes)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


def load(logdir: str, window_name: str, scopes: dict | None = None) -> dict:
    """Read the newest trace under ``logdir``.

    Args:
      logdir: the directory given to ``jax.profiler.start_trace``.
      window_name: the host span that marks the traced window.
      scopes: ``hlo_scopes`` of the programs that ran, to name the scope
        of ops whose trace statistics carry none.

    Host spans are kept when their names start with ``bench/``: the
    benchmark's own, which name the idle gaps.
    """
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no trace under {logdir}")
    pd = ProfileData.from_file(paths[-1])
    scopes = scopes or {}
    out = {"window": None, "devices": {}, "modules": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            mods = ([[ev.start_ns, ev.duration_ns, ev.name.split("(")[0]]
                     for ev in lines["XLA Modules"].events]
                    if "XLA Modules" in lines else [])
            ops = []
            for ev in (lines["XLA Ops"].events if "XLA Ops" in lines
                       else ()):
                st = _stats(ev)
                m = _OP_NAME.match(ev.name)
                op = str(st.get("hlo_op") or (m.group(1) if m
                                              else ev.name))
                module = str(st.get("hlo_module")
                             or _covering(mods, ev.start_ns))
                scope = str(st.get("tf_op") or scopes.get(module, {})
                            .get(op, ""))
                ops.append([ev.start_ns, ev.duration_ns, op, module,
                            scope])
            if ops:
                out["devices"][plane.name] = ops
                out["modules"][plane.name] = mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == window_name:
                        out["window"] = [ev.start_ns,
                                         ev.start_ns + ev.duration_ns]
                    if ev.name.startswith("bench/"):
                        out["host"].append([ev.start_ns, ev.duration_ns,
                                            ev.name])
    return out


def _covering(mods: list, t: float) -> str:
    """The module whose execution covers time ``t``."""
    for s, d, name in mods:
        if s <= t <= s + d:
            return name
    return ""


def _union(intervals: list, lo: float, hi: float) -> float:
    """Length of the union of ``[start, end)`` intervals clipped to
    ``[lo, hi)``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(intervals: list, lo: float, hi: float) -> list:
    """Idle ``(start, end)`` stretches of ``[lo, hi)`` between intervals."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def _host_name(host: list, s: float, e: float) -> str:
    """The innermost benchmark span covering the middle of a gap."""
    mid = (s + e) / 2
    best = None
    for hs, hd, name in host:
        if hs <= mid <= hs + hd and (best is None or hd < best[0]):
            best = (hd, name)
    return best[1] if best else "outside any span"


def reduce(trace: dict) -> dict:
    """The numbers a trace gives, per device averaged over devices.

    Returns ``{"window_s", "busy_s", "idle_share", "scope_s": {scope
    path: seconds}, "op_s": {op label: seconds}, "module_calls": {module:
    [call seconds, ...]}, "gaps": [[host span, seconds, count, longest
    seconds], ...]}``, gaps longest in total first.
    """
    lo, hi = trace["window"]
    window = hi - lo
    devices = trace["devices"]
    busy, scope_s, op_s, gaps, calls = 0.0, {}, {}, {}, {}
    for plane, ops in devices.items():
        spans = [(s, s + d) for s, d, *_ in ops]
        busy += _union(spans, lo, hi)
        for s, d, op, _module, scope in ops:
            inside = max(0.0, min(s + d, hi) - max(s, lo))
            if not inside:
                continue
            scope_s[scope] = scope_s.get(scope, 0.0) + inside
            label = _op_label(op, scope)
            op_s[label] = op_s.get(label, 0.0) + inside
        for gs, ge in _gaps(spans, lo, hi):
            name = _host_name(trace["host"], gs, ge)
            gaps.setdefault(name, []).append(ge - gs)
        for s, d, module in trace["modules"].get(plane, []):
            if lo <= s and s + d <= hi:
                calls.setdefault(module, []).append(d / 1e9)
    n = max(len(devices), 1)
    per = lambda x: x / n / 1e9                       # noqa: E731
    return {
        "window_s": window / 1e9,
        "busy_s": per(busy),
        "idle_share": (1.0 - busy / n / window if window and devices
                       else None),
        "scope_s": {k: per(v) for k, v in scope_s.items()},
        "op_s": {k: per(v) for k, v in op_s.items()},
        "module_calls": calls,
        "gaps": sorted(([k, per(sum(v)), len(v), per(max(v))]
                        for k, v in gaps.items()), key=lambda g: -g[1]),
    }


def _op_label(op: str, scope: str) -> str:
    """An op's name for the breakdown: its scope path without the
    ``jit(...)`` wrappers and transforms, then the HLO op's kind."""
    kind = re.sub(r"[.\d]+$", "", op)
    path = [p for p in scope.split("/") if p and not p.startswith(
        ("jit(", "pjit(", "jvp(", "transpose(", "vmap(", "checkpoint",
         "remat", "while", "body", "cond"))]
    return "/".join(path[:-1] + [kind]) if path else kind


def breakdown(red: dict) -> dict:
    """The result line's ``breakdown``: the ten device ops that took most
    time, and the ten longest idle stretches by host span."""
    ops = sorted(red["op_s"].items(), key=lambda kv: -kv[1])[:10]
    gaps = [[name, secs] for name, secs, _count, _longest in
            red["gaps"][:10]]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": gaps}


def scope_seconds(red: dict, *parts: str) -> float:
    """Device seconds of ops whose scope path holds any of ``parts`` as
    whole path components (``"agg"`` matches ``jit(step)/agg/gram/dot``)."""
    pats = [re.compile(r"(^|/)" + re.escape(p) + r"(/|$)") for p in parts]
    return sum(v for k, v in red["scope_s"].items()
               if any(p.search(k) for p in pats))
