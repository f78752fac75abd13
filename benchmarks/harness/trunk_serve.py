"""Cells whose bank is a shared trunk with per-machine projections
(configuration ``keye_trunk300``), scoring requests over HTTP.

``serve.py`` and ``weights.py`` make every leaf per member and cannot stage
a shared trunk. This driver stages ONE trunk artifact beside the members'
stubs (``trunk.pkl``: like a member's stub, not the 7.5 GB of weights but
the call that remakes them from the seed where the server unpickles it, a
leaf at a time) and from there is ``serve.py``'s: the same ``Served``, the
same child load generator, the same window. After the window it compares a
seeded sample of the window's own answers with the family's plain
reference: the six arrays, and the two kinds of selections the answer
carries (which experts each row was routed to, which keys every 64th row
attended to), as the share that disagrees with the reference's. A traced
run adds the device seconds by ``jax.named_scope`` (``scope_trace.py``) and
the counters the bank keeps for buckets with shared leaves.
"""

import asyncio
import gc
import io
import json
import os
import pickle
import time
from typing import Dict, List, Optional

import numpy as np

import families
from harness import check, common, serve, spec, trace, weights, wire
from harness import scope_trace

SCOPES = (
    "trunk/project", "trunk/indexer", "trunk/select", "trunk/attend", "trunk/route",
    "trunk/experts", "trunk/combine", "member/in_proj", "member/head",
)


def witness_stride(config: dict) -> int:
    """Every 64th query's selection rides the answer (docs/observability.md);
    every chunk's last where a chunk is shorter (CPU tests)."""
    return min(64, int(config["sa_config"]["q_chunk_size"]))


def make_trunk(config: dict, seed: int):
    return families.load(config["family"], "layout").trunk_to_program(config, seed)


class _TrunkStub:
    """What ``trunk.pkl`` holds (``adapter._MemberStub`` for the trunk)."""

    def __init__(self, config: dict, seed: int):
        self.args = (config, seed)

    def __reduce__(self):
        return make_trunk, self.args


def stage_trunk(config: dict, seed: int, model_dir: str) -> None:
    path = os.path.join(model_dir, config["trunk_artifact"])
    os.makedirs(path)
    with open(os.path.join(path, "trunk.pkl"), "wb") as fh:
        pickle.dump(_TrunkStub(config, seed), fh)


_start_members_server = serve.start_server  # bound now: a tool may point ``serve``'s at ours


async def start_server(cell: spec.Cell, seed: int, traced: bool, on_tpu: bool, work: str):
    """``serve.start_server`` with the trunk artifact in place first."""
    stage_trunk(cell.config, seed, os.path.join(work, "models"))
    return await _start_members_server(cell, seed, traced, on_tpu, work)


# ------------------------------------------------------------- reference


def reference_answer(config: dict, seed: int, w: Dict[str, np.ndarray], X: np.ndarray,
                     **how) -> Dict[str, np.ndarray]:
    """The six arrays and the selections for one request, by the family's
    plain reference: input scaling, the trunk's forecast, absolute error in
    model space against the NEXT row, error scaling, row norms."""
    import jax.numpy as jnp

    layout = families.load(config["family"], "layout")
    forward = families.load(config["family"], "forward")
    xs = (np.asarray(X, np.float32) - w["in_shift"]) * w["in_scale"]
    stride = witness_stride(config)
    sampled = np.arange(stride - 1, len(xs), stride)
    got = forward.forecast(
        config, lambda l: layout.trunk_layer(config, seed, l),
        {k: jnp.asarray(v) for k, v in w.items()}, xs, sampled, **how,
    )
    recon = np.asarray(got["out"])[:-1]
    diff = np.abs(xs[1:] - recon)
    scaled = (diff - w["err_shift"]) * w["err_scale"]
    return {
        "model-input": np.asarray(X[1:], np.float32), "model-output": recon,
        "tag-anomaly-unscaled": diff, "tag-anomaly-scaled": scaled,
        "total-anomaly-unscaled": np.sqrt(np.sum(diff * diff, axis=-1)),
        "total-anomaly-scaled": np.sqrt(np.sum(scaled * scaled, axis=-1)),
        "experts": np.asarray(got["experts"]),  # (L, T, E) bool
        "keys": np.asarray(got["keys"]),  # (L, sampled, T) bool
    }


def as_answer(ref: Dict[str, np.ndarray], top_k: int) -> Dict[str, np.ndarray]:
    """A reference answer in the frames a server's answer has, so that a
    control or a planted fault can stand in the program's place."""
    out = {name: ref[name] for name in serve.COMPARED + ("model-input",)}
    # ids of the kept experts, padded with a kept one where a fault kept fewer
    order = np.argsort(~ref["experts"], axis=-1, kind="stable")[..., :top_k]
    kept = np.take_along_axis(ref["experts"], order, axis=-1)
    out["expert-selection"] = np.where(kept, order, order[..., :1]).astype(np.uint8)
    out["key-selection"] = np.packbits(ref["keys"], axis=-1, bitorder="little")
    return out


def selection_gaps(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]) -> Dict[str, float]:
    """Share of the selections that do not agree: of the program's (row,
    expert) choices, those the reference did not make; of the (query, key)
    pairs either selected for the sampled queries, those not in both."""
    rows = want["experts"].shape[1]
    chosen = np.asarray(got["expert-selection"]).astype(np.int64)  # (L, rows, k)
    if chosen.shape[:2] != want["experts"].shape[:2]:
        return {"expert_selection_gap": float("inf"), "key_selection_gap": float("inf")}
    # a row that names an expert twice (a fault that kept fewer) agrees once
    chosen = np.sort(chosen, axis=-1)
    distinct = np.concatenate(
        [np.ones_like(chosen[..., :1], bool), np.diff(chosen, axis=-1) != 0], axis=-1
    )
    hits = np.take_along_axis(want["experts"], chosen, axis=-1) & distinct
    expert_gap = 1.0 - float(hits.sum()) / hits.size
    n = want["keys"].shape[1]
    bits = np.unpackbits(np.asarray(got["key-selection"]), axis=-1, bitorder="little")
    keys = bits[:, :n, :rows].astype(bool)
    both = float(np.sum(keys & want["keys"]))
    key_gap = 1.0 - both / max(float(keys.sum()), float(want["keys"].sum()), 1.0)
    return {"expert_selection_gap": expert_gap, "key_selection_gap": key_gap}


def _sample_reference(config: dict, seed: int, rows: int, meta: dict, **how):
    return reference_answer(
        config, seed, weights.member_weights(config, seed, meta["member"]),
        weights.request_body(config, seed, meta["body"], rows), **how,
    )


def compare_answers(config: dict, seed: int, rows: int, samples: List[dict],
                    answers: List[Dict[str, np.ndarray]],
                    wants: Optional[List[Dict[str, np.ndarray]]] = None) -> Dict[str, float]:
    """Each sampled answer against the reference run once over the same
    request for the same machine (weights and body remade from the seed);
    ``wants``: those references, where the caller has them already."""
    empty = 0.0 if answers else float("inf")  # nothing compared proves nothing
    numbers = {name: empty for name in (
        "input_echo_gap", "output_gap", "score_gap", "expert_selection_gap", "key_selection_gap")}
    for k, (meta, got) in enumerate(zip(samples, answers)):
        want = wants[k] if wants else _sample_reference(config, seed, rows, meta)
        worst = lambda name, value: numbers.__setitem__(name, max(numbers[name], value))
        worst("input_echo_gap", check.sup_gap(got["model-input"], want["model-input"]))
        worst("output_gap", check.rel_l2_gap(got["model-output"], want["model-output"]))
        for name in serve.COMPARED[1:]:
            worst("score_gap", check.rel_l2_gap(got[name], want[name]))
        for name, value in selection_gaps(got, want).items():
            worst(name, value)
    return numbers


# ------------------------------------------------------------------- run


async def _serve(cell, seed, seconds, traced, t_start, on_tpu, work):
    served = await start_server(cell, seed, traced, on_tpu, work)
    try:
        got = await served.window(cell.traffic, seconds, t_start, traced)
        got["memory_peak"] = common.memory_peak_bytes()
        got["spans"] = serve._span_ms(served.app)
        got["shared"] = dict(served.app["bank"].shared_stats)
    finally:
        await served.runner.cleanup()
    return got


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool, t_start: float,
        on_tpu: bool = True) -> dict:
    work = common.work_dir()
    try:
        got = asyncio.run(_serve(cell, seed, seconds, traced, t_start, on_tpu, work))
        gc.collect()  # the app, its bank, the trunk and the stacked leaves are unreferenced now
        from gordo_components_tpu import serializer

        serializer.release_trunks()  # the artifact cache held the trunk: the reference needs the room
        summary = got["summary"]
        arrays = np.load(io.BytesIO(got["blob"]))
        answers = [
            wire.unpack(arrays[f"resp_{i}"].tobytes()) for i in range(len(summary["samples"]))
        ]
        rows = int(cell.traffic["request_rows"])
        t_ref = time.monotonic()
        numbers = compare_answers(cell.config, seed, rows, summary["samples"], answers)
        numbers["answers_compared"] = float(len(answers))
        print(f"reference over {len(answers)} answers: {time.monotonic() - t_ref:.2f}s", flush=True)
        checks = check.verdict(numbers, cell.limits)
        latency, late = arrays["latency_ms"], arrays["late_ms"]
        if len(latency):
            print("latency percentiles [50, 90, 95, 99] ms: "
                  f"{[round(float(v), 3) for v in np.percentile(latency, [50, 90, 95, 99])]}")
        print(f"window: attempted {summary['attempted']} failed {summary['failed']} "
              f"completed in window {summary['completed_in_window']} "
              f"drain {summary['drain_s']:.2f}s, generator late p99 "
              f"{np.percentile(late, 99) if len(late) else float('nan'):.2f} ms; "
              f"shared-leaf counters {got['shared']}", flush=True)
        values = {"setup_s": got["setup_s"]}
        if len(latency):
            values["score_p50_ms"] = float(np.percentile(latency, 50))
            values["score_p95_ms"] = float(np.percentile(latency, 95))
        obs = {
            "config": cell.config, "traffic": cell.traffic, "window_s": summary["window_s"],
            "spans": got["spans"], "late_ms": late, "latency_ms": latency,
            "engine": got["engine"], "shared": got["shared"],
            "rows_completed": summary["rows_completed_in_window"],
            "requests_completed": summary["completed_in_window"],
            "request_rows": rows,
        }
        if traced:
            path = trace.find_xplane(got["window"].log_dir)
            obs["scopes"] = scope_trace.reduce_file(path, SCOPES) if path else {}
            obs["trace"] = got["window"].reduce()
            obs["traced_window_s"] = got["window"].window_s
            obs["peaks"] = spec.peaks_for(common.device_block()["kind"]) if obs["trace"] else None
            print(f"device seconds by scope over {obs['traced_window_s']:.2f}s: "
                  f"{json.dumps({k: round(v, 4) for k, v in obs['scopes'].items()})}", flush=True)
    finally:
        common.remove(work)
    return common.emit(
        cell, traced, values, obs, summary["attempted"], summary["failed"], checks,
        got["memory_peak"],
    )


def control_readings(cell: spec.Cell, seeds, requests: Optional[int] = None) -> List[dict]:
    """On the chip at the cell's own size, with no server: as many requests
    as a run compares, for machines and bodies drawn from each seed,
    computed by the reference with bfloat16 operands (what the
    configuration states: the arithmetic alone, no program), by the control
    (float8 e4m3 operands, one precision below) and with each planted
    fault, each read against the reference."""
    forward = families.load(cell.config["family"], "forward")
    config, rows = cell.config, int(cell.traffic["request_rows"])
    top_k = int(config["num_experts_per_tok"])
    out = []
    for seed in seeds:
        rng = weights.rng_for(seed, weights.SAMPLE)
        samples = [
            {"member": int(rng.integers(int(config["bank_members"]))), "body": k}
            for k in range(requests or int(cell.traffic["check_requests"]))
        ]
        row = {"seed": seed}
        variants = [("stated_bf16", dict(operands="bfloat16")),
                    ("control_e4m3", dict(operands="float8_e4m3fn"))]
        variants += [("fault_" + f, dict(operands="bfloat16", fault=f)) for f in forward.FAULTS]
        wants = [_sample_reference(config, seed, rows, m) for m in samples]
        for label, how in variants:
            answers = [
                as_answer(_sample_reference(config, seed, rows, m, **how), top_k) for m in samples
            ]
            row[label] = compare_answers(config, seed, rows, samples, answers, wants)
            print(json.dumps({"seed": seed, label: row[label]}), flush=True)
        out.append(row)
    return out
