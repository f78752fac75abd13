"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the metrics
need: busy seconds of each device (union of the intervals in which an XLA
op ran), device seconds per XLA module (program), the longest device ops,
and the longest idle gaps named by the benchmark's own host annotation
that covers them.
"""

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def _union(intervals: List[Tuple[float, float]]) -> Tuple[float, List[Tuple[float, float]]]:
    """Total covered length and the gaps between merged intervals."""
    total, gaps = 0.0, []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            total += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        total += cur_e - cur_s
    return total, gaps


def _strip(name: str) -> str:
    """``jit_run_epoch(1234567)`` -> ``jit_run_epoch``."""
    return name.split("(")[0]


_OP = re.compile(r"^%?(\S+) = \(?(\w+\[[\d,]*\])?.*?\s([\w\-]+)\(")


def short_op(name: str) -> str:
    """An XLA op event is named by its whole HLO line; keep the op's name,
    its (first) result shape and its opcode:
    ``copy.36 bf16[4096,250,300] copy``."""
    m = _OP.match(name)
    if not m:
        return name[:80]
    return " ".join(part for part in m.groups() if part)[:80]


def reduce_planes(planes, annotations_prefix: str = "bench:") -> dict:
    """``planes``: an iterable of objects with ``name`` and ``lines``; a
    line has ``name`` and ``events``; an event has ``name``, ``start_ns``,
    ``duration_ns`` (``jax.profiler.ProfileData``, or a fake in tests)."""
    devices = {}
    host_spans: List[Tuple[float, float, str]] = []
    for plane in planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules = [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
            devices[plane.name] = (ops, modules)
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(annotations_prefix):
                        host_spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    if not devices:
        return {}
    busy, op_seconds, module_seconds, module_calls, gaps = [], {}, {}, {}, []
    for ops, modules in devices.values():
        covered, dev_gaps = _union([(s, e) for s, e, _ in ops])
        busy.append(covered / 1e9)
        gaps.extend(dev_gaps)
        for s, e, name in ops:
            name = short_op(name)
            if name.endswith(" while"):
                continue  # a loop's own event spans the ops of its body, listed themselves
            op_seconds[name] = op_seconds.get(name, 0.0) + (e - s) / 1e9
        for s, e, name in modules:
            key = _strip(name)
            module_seconds[key] = module_seconds.get(key, 0.0) + (e - s) / 1e9
            module_calls[key] = module_calls.get(key, 0) + 1
    n = len(devices)
    idle_by_host: Dict[str, float] = {}
    for s, e in gaps:
        # the innermost (shortest) benchmark annotation that covers the gap
        covering = [(he - hs, name) for hs, he, name in host_spans if hs <= s and e <= he]
        label = min(covering)[1] if covering else "unannotated"
        idle_by_host[label] = idle_by_host.get(label, 0.0) + (e - s) / 1e9 / n
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "busy_s": sum(busy) / n,
        "n_devices": n,
        "module_seconds": {k: v / n for k, v in module_seconds.items()},
        "module_calls": module_calls,
        "device_ops": top({k: v / n for k, v in op_seconds.items()}),
        "idle_gaps": top(idle_by_host),
    }


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes)
