"""Cells whose bank is a latent-attention trunk of which the chip holds a
share of the routed experts (configuration ``axk1_trunk300``), scoring
requests over HTTP.

``trunk_serve.py`` reads ``config["sa_config"]``, sums an indexer's scopes
and compares key selections, none of which this family has. This driver
takes ``trunk_serve``'s staging and serving as they are (``stage_trunk``
through ``start_server``, ``_serve``: one trunk artifact beside the
members' stubs, ``serve.py``'s ``Served``, child load generator and
window) and has its own scopes and comparison. After the window it compares
a seeded sample of the window's own answers with the family's plain
reference, which is given the same share of the experts: the six arrays,
and which 8 of the 192 experts each row was routed to in every routed
layer, as the share of the program's choices the reference did not make. A
traced run adds the device seconds by ``jax.named_scope``
(``scope_trace.py``) and the counters the bank keeps for buckets with
shared leaves (``routed_pairs``, ``held_pairs``, ``held_tokens_busiest``).
"""

import asyncio
import gc
import io
import json
import time
from typing import Dict, List, Optional

import numpy as np

import families
from harness import check, common, scope_trace, serve, spec, trace, trunk_serve, weights, wire

SCOPES = (
    "trunk/project", "trunk/attend", "trunk/dense_mlp", "trunk/shared_expert", "trunk/route",
    "trunk/experts", "trunk/combine", "member/in_proj", "member/head",
)
GAPS = ("input_echo_gap", "output_gap", "score_gap", "expert_selection_gap")


def reference_answer(config: dict, seed: int, w: Dict[str, np.ndarray], X: np.ndarray,
                     **how) -> Dict[str, np.ndarray]:
    """The six arrays and the experts kept for one request, by the family's
    plain reference: input scaling, the trunk's forecast, absolute error in
    model space against the NEXT row, error scaling, row norms."""
    import jax.numpy as jnp

    layout = families.load(config["family"], "layout")
    forward = families.load(config["family"], "forward")
    xs = (np.asarray(X, np.float32) - w["in_shift"]) * w["in_scale"]
    got = forward.forecast(
        config, lambda l: layout.trunk_layer(config, seed, l),
        {k: jnp.asarray(v) for k, v in w.items()}, xs, **how,
    )
    recon = np.asarray(got["out"])[:-1]
    diff = np.abs(xs[1:] - recon)
    scaled = (diff - w["err_shift"]) * w["err_scale"]
    return {
        "model-input": np.asarray(X[1:], np.float32), "model-output": recon,
        "tag-anomaly-unscaled": diff, "tag-anomaly-scaled": scaled,
        "total-anomaly-unscaled": np.sqrt(np.sum(diff * diff, axis=-1)),
        "total-anomaly-scaled": np.sqrt(np.sum(scaled * scaled, axis=-1)),
        "experts": np.asarray(got["experts"]),  # (routed layers, T, E) bool
    }


def as_answer(ref: Dict[str, np.ndarray], top_k: int) -> Dict[str, np.ndarray]:
    """A reference answer in the frames a server's answer has, so that a
    control or a planted fault can stand in the program's place."""
    out = {name: ref[name] for name in serve.COMPARED + ("model-input",)}
    # ids of the kept experts, padded with a kept one where a fault kept fewer
    order = np.argsort(~ref["experts"], axis=-1, kind="stable")[..., :top_k]
    kept = np.take_along_axis(ref["experts"], order, axis=-1)
    out["expert-selection"] = np.where(kept, order, order[..., :1]).astype(np.uint8)
    return out


def expert_selection_gap(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]) -> float:
    """Share of the program's (row, expert) choices, of ALL the published
    experts and in every routed layer, that the reference did not make."""
    chosen = np.asarray(got.get("expert-selection", np.zeros((0, 0, 0)))).astype(np.int64)
    if chosen.shape[:2] != want["experts"].shape[:2]:
        return float("inf")
    # a row that names an expert twice (a fault that kept fewer) agrees once
    chosen = np.sort(chosen, axis=-1)
    distinct = np.concatenate(
        [np.ones_like(chosen[..., :1], bool), np.diff(chosen, axis=-1) != 0], axis=-1
    )
    hits = np.take_along_axis(want["experts"], chosen, axis=-1) & distinct
    return 1.0 - float(hits.sum()) / hits.size


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
    numbers = dict.fromkeys(GAPS, empty)
    for k, (meta, got) in enumerate(zip(samples, answers)):
        want = wants[k] if wants else _sample_reference(config, seed, rows, meta)
        worst = lambda name, value: numbers.__setitem__(name, max(numbers[name], value))
        worst("input_echo_gap", check.sup_gap(got["model-input"], want["model-input"]))
        worst("output_gap", check.rel_l2_gap(got["model-output"], want["model-output"]))
        for name in serve.COMPARED[1:]:
            worst("score_gap", check.rel_l2_gap(got[name], want[name]))
        worst("expert_selection_gap", expert_selection_gap(got, want))
    return numbers


# ------------------------------------------------------------------- run


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool, t_start: float,
        on_tpu: bool = True) -> dict:
    work = common.work_dir()
    try:
        got = asyncio.run(trunk_serve._serve(cell, seed, seconds, traced, t_start, on_tpu, work))
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


def control_readings(cell: spec.Cell, seeds, requests: Optional[int] = None,
                     only: Optional[List[str]] = None) -> List[dict]:
    """On the chip at the cell's own size, with no server: as many requests
    as a run compares, for machines and bodies drawn from each seed,
    computed by the reference with bfloat16 operands (what the
    configuration states: the arithmetic alone, no program), by the control
    (float8 e4m3 operands, one precision below) and with each planted
    fault (``only``: those of these labels), each read against the reference."""
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
        variants = [v for v in variants if only is None or v[0] in only]
        wants = [_sample_reference(config, seed, rows, m) for m in samples]
        for label, how in variants:
            answers = [
                as_answer(_sample_reference(config, seed, rows, m, **how), top_k) for m in samples
            ]
            row[label] = compare_answers(config, seed, rows, samples, answers, wants)
            print(json.dumps({"seed": seed, label: row[label]}), flush=True)
        out.append(row)
    return out
