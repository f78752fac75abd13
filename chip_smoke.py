#!/usr/bin/env python3
"""The quickest proof that the product path still runs on the chip.

``python3 chip_smoke.py`` (one TPU chip, no arguments) drives the system's
main path once through the entry points a user calls:

1. **train** — CLI ``build-fleet`` on one documented gang
   (``models_per_gang: 1024``): 1024 ``RandomDataset`` machines x 10 tags
   x 1440 rows with the default ``DiffBasedAnomalyDetector`` hourglass
   autoencoder, plus 64 ``LSTMAutoEncoder`` (``lstm_hourglass``,
   lookback 12) machines so a sequence bucket with M > 1 exists;
2. **serve** — ``build_app`` on those artifacts with default settings,
   on a real localhost port; waits for the bank's warm-up compile;
3. **score** — HTTP ``POST .../anomaly/prediction`` (JSON and
   ``application/x-gordo-tensor``) for a dense and an LSTM member, then a
   concurrent burst of 64 requests for 64 different members, each answer
   compared with the same artifact scored on the per-model path
   (``serializer.load(dir).anomaly(X)``).

It fails (non-zero exit, no result line) when JAX finds no TPU, when any
phase raises, or when a device decision is not the one a TPU makes: every
member banked, ``kernel=pallas``, the LSTM bucket ``time_major`` with the
fused ``pallas`` step, nothing on a fallback path. Everything runs in this
one process — a chip belongs to one process at a time.

``--four-chips`` runs ONLY the sharded paths and what they are compared
with: the same gang trained over a four-device ``models`` mesh, the bank
sharded by ``devices=4``, the same requests, against a single-device bank
built in this process on device 0.

The LAST stdout line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
everything else is on earlier lines. Data is made from ``--seed``; no
network; the only processes started are the host-staging workers
``build-fleet`` itself joins.
"""

import argparse
import asyncio
import contextlib
import io
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

PROJECT = "smoke"
# one documented gang (examples/fleet.yaml ``models_per_gang: 1024``) of the
# default detector at the bench_fleet shape, plus a sequence gang
FULL_SIZES = dict(
    n_dense=1024, n_lstm=64, n_tags=10, rows=1440, epochs=4,
    request_rows=256, burst=64,
)
# the device decisions a TPU backend makes with default settings
TPU_EXPECT = dict(kernel="pallas", seq_kernel="pallas", seq_layout="time_major")
LOOKBACK = 12
# bank-vs-per-model agreement: the tolerance tests/test_bank.py holds the
# two paths to (pd.testing.assert_frame_equal(rtol=1e-4, atol=1e-5)) ...
RTOL, ATOL = 1e-4, 1e-5
# ... on all but a small share of each array. The TPU's default f32 matmul
# rounds its operands to bf16, and the two paths are different programs
# (the bank's batched / time-major fused forward vs the per-model flax
# forward): where an operand sits on a bf16 rounding boundary, fp32-level
# reordering rounds it up in one and down in the other — one bf16 ULP
# (2^-7 relative) in that operand, carried through the remaining layers
# and time steps. First chip run, PR 22: 9 of 2450 LSTM outputs, at most
# 1.0e-2 relative. So: at most FLIP_SHARE of an array may miss the tight
# tolerance, and every element stays within a few bf16 ULPs (scores are
# O(1): inputs are min-max scaled).
BF16_ULP = 2.0 ** -7
FLIP_SHARE, FLIP_RTOL, FLIP_ATOL = 0.02, 4 * BF16_ULP, BF16_ULP


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ------------------------------------------------------------------ #
# phase 1: train (CLI build-fleet)
# ------------------------------------------------------------------ #


def _detector(estimator: dict) -> dict:
    return {
        "gordo_components_tpu.models.DiffBasedAnomalyDetector": {
            "base_estimator": {
                "sklearn.pipeline.Pipeline": {
                    "steps": ["sklearn.preprocessing.MinMaxScaler", estimator]
                }
            }
        }
    }


def machines_payload(sizes: dict, seed: int) -> dict:
    """The gang as a ``build-fleet --machines-file`` payload: ``d-NNNN``
    dense members (the default hourglass autoencoder) and ``l-NNNN`` LSTM
    members (examples/fleet.yaml's ``turbine-lstm`` model), each over its
    own seeded RandomDataset."""
    import pandas as pd

    start = pd.Timestamp("2020-01-01T00:00:00Z")
    end = start + pd.Timedelta(minutes=10) * sizes["rows"]  # 10-minute rows
    dense = _detector(
        {
            "gordo_components_tpu.models.AutoEncoder": {
                "kind": "feedforward_hourglass", "epochs": sizes["epochs"],
            }
        }
    )
    lstm = _detector(
        {
            "gordo_components_tpu.models.LSTMAutoEncoder": {
                "kind": "lstm_hourglass", "lookback_window": LOOKBACK,
                "epochs": sizes["epochs"],
            }
        }
    )
    machines = []
    for prefix, count, model in (
        ("d", sizes["n_dense"], dense), ("l", sizes["n_lstm"], lstm),
    ):
        for i in range(count):
            name = f"{prefix}-{i:04d}"
            machines.append(
                {
                    "name": name,
                    "model": model,
                    "dataset": {
                        "type": "RandomDataset",
                        "train_start_date": start.isoformat(),
                        "train_end_date": end.isoformat(),
                        "tag_list": [
                            f"{name}-tag-{j}" for j in range(sizes["n_tags"])
                        ],
                        "seed": seed + len(machines),
                    },
                }
            )
    return {"machines": machines}


def phase_train(workdir: str, sizes: dict, seed: int, expect: dict) -> dict:
    """``build-fleet`` through the click CLI, in this process. Returns the
    build manifest plus ``model_dir`` and the member names per family."""
    from gordo_components_tpu import serializer
    from gordo_components_tpu.cli.cli import gordo

    machines_file = os.path.join(workdir, "machines.json")
    model_dir = os.path.join(workdir, "models")
    payload = machines_payload(sizes, seed)
    with open(machines_file, "w") as fh:
        json.dump(payload, fh)
    n_total = sizes["n_dense"] + sizes["n_lstm"]
    t0 = time.time()
    echoed = io.StringIO()  # the command echoes the whole manifest
    try:
        with contextlib.redirect_stdout(echoed):
            gordo.main(
                args=[
                    "--log-level", "WARNING", "build-fleet",
                    "--machines-file", machines_file, "--output-dir", model_dir,
                ],
                standalone_mode=False,
            )
    except SystemExit as exc:  # the command's partial/failed exit codes
        if exc.code not in (0, None):
            raise RuntimeError(
                f"build-fleet exited {exc.code}: {echoed.getvalue()[-2000:]}"
            ) from None
    wall = time.time() - t0
    with open(os.path.join(model_dir, "build_manifest.json")) as fh:
        manifest = json.load(fh)
    check(manifest["n_failed"] == 0, f"failed members: {manifest['failed']}")
    check(manifest["n_built"] == n_total, f"built {manifest['n_built']}/{n_total}")
    buckets = {b["model_type"]: b for b in manifest["buckets"]}
    check(
        buckets["AutoEncoder"]["n_members"] == sizes["n_dense"]
        and buckets["LSTMAutoEncoder"]["n_members"] == sizes["n_lstm"],
        f"unexpected trainer buckets: {manifest['buckets']}",
    )
    check(
        buckets["LSTMAutoEncoder"]["layout"] == expect["seq_layout"],
        f"LSTM gang trained with layout {buckets['LSTMAutoEncoder']['layout']!r}, "
        f"expected {expect['seq_layout']!r}",
    )
    names = [m["name"] for m in payload["machines"]]
    epoch_seconds = {}
    for name in names:
        meta = serializer.load_metadata(os.path.join(model_dir, name))["model"]
        losses = meta["history"]["loss"]
        check(
            len(losses) == sizes["epochs"] and bool(np.isfinite(losses).all()),
            f"{name}: losses {losses}",
        )
        epoch_seconds.setdefault(
            name[0], meta["fleet_stats"]["buckets"][0]["epoch_seconds"]
        )
    say(
        f"train: build-fleet built {manifest['n_built']}/{n_total} members in "
        f"{wall:.1f}s wall, n_failed=0, all losses finite, "
        f"gang_width={manifest['gang_width']}, device={manifest['device']}"
    )
    for fam, key in (("dense", "d"), ("lstm", "l")):
        b = buckets["AutoEncoder" if key == "d" else "LSTMAutoEncoder"]
        # the first epochs include the epoch program's compiles; the last
        # is the warm repeat
        say(
            f"train[{fam}]: M={b['n_members']} layout={b['layout']} "
            f"epoch seconds {[round(s, 3) for s in epoch_seconds[key]]} "
            f"(first call {epoch_seconds[key][0]:.2f}s, warm repeat "
            f"{epoch_seconds[key][-1]:.3f}s)"
        )
    return dict(
        manifest,
        model_dir=model_dir,
        dense=[n for n in names if n.startswith("d-")],
        lstm=[n for n in names if n.startswith("l-")],
        wall_s=wall,
    )


# ------------------------------------------------------------------ #
# phases 2+3: serve (build_app) and score over HTTP
# ------------------------------------------------------------------ #


def _request_rows(name: str, sizes: dict, seed: int) -> np.ndarray:
    rng = np.random.RandomState(seed + int(name[2:]) + (7919 if name[0] == "l" else 0))
    return rng.rand(sizes["request_rows"], sizes["n_tags"]).astype(np.float32)


def _reference(model_dir: str, name: str, X: np.ndarray) -> dict:
    """The same artifact on the per-model path."""
    from gordo_components_tpu import serializer
    from gordo_components_tpu.server.model_io import anomaly_frame_arrays

    model = serializer.load(os.path.join(model_dir, name))
    return anomaly_frame_arrays(model.anomaly(X))


def _compare(name: str, got: dict, want: dict) -> np.ndarray:
    """Finite, same shape, and in agreement with the reference (see
    ``FLIP_SHARE``); returns ``[largest |diff|, largest share of an array
    outside the tight tolerance]``."""
    from gordo_components_tpu.utils.wire import ANOMALY_FRAME_NAMES

    worst_abs = worst_share = 0.0
    for key in ANOMALY_FRAME_NAMES:
        g, w = np.asarray(got[key], np.float64), np.asarray(want[key], np.float64)
        check(g.shape == w.shape, f"{name}/{key}: shape {g.shape} != {w.shape}")
        check(bool(np.isfinite(g).all()), f"{name}/{key}: non-finite values")
        share = 1.0 - float(np.isclose(g, w, rtol=RTOL, atol=ATOL).mean())
        check(
            share <= FLIP_SHARE,
            f"{name}/{key}: {share:.2%} of elements outside rtol={RTOL} "
            f"atol={ATOL} of the reference (max |diff| {np.abs(g - w).max():.3g})",
        )
        np.testing.assert_allclose(
            g, w, rtol=FLIP_RTOL, atol=FLIP_ATOL, err_msg=f"{name}/{key}"
        )
        worst_abs = max(worst_abs, float(np.abs(g - w).max()))
        worst_share = max(worst_share, share)
    return np.array([worst_abs, worst_share])


async def _post(http, base: str, name: str, X: np.ndarray, encoding: str) -> dict:
    """One ``POST .../anomaly/prediction``; returns the six score arrays."""
    from gordo_components_tpu.server.model_io import anomaly_frame_arrays
    from gordo_components_tpu.server.utils import dict_to_frame
    from gordo_components_tpu.utils.wire import (
        TENSOR_CONTENT_TYPE,
        pack_frames,
        unpack_frames,
    )

    url = f"{base}/{name}/anomaly/prediction"
    if encoding == "tensor":
        kwargs = dict(
            data=pack_frames([("X", X)]),
            headers={"Content-Type": TENSOR_CONTENT_TYPE},
        )
    else:
        kwargs = dict(json={"X": X.tolist()})
    async with http.post(url, **kwargs) as resp:
        raw = await resp.read()
        check(resp.status == 200, f"{name} [{encoding}]: HTTP {resp.status} {raw[:300]!r}")
    if encoding == "tensor":
        return unpack_frames(raw)
    return anomaly_frame_arrays(dict_to_frame(json.loads(raw)))


@contextlib.asynccontextmanager
async def _served(model_dir: str, devices=None):
    """``build_app`` on a real localhost port (what ``run_server`` hands to
    ``web.run_app``), warm-up awaited: a failed warm-up compile raises here."""
    from aiohttp import ClientSession, web

    from gordo_components_tpu.server import build_app

    t0 = time.time()
    app = build_app(model_dir, devices=devices)
    t_build = time.time() - t0
    runner = web.AppRunner(app)
    await runner.setup()
    try:
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = runner.addresses[0][1]
        t1 = time.time()
        warmup = app.get("warmup_future")
        check(warmup is not None, "the server started no bank warm-up")
        await warmup
        say(
            f"serve: build_app (load + bank build) {t_build:.1f}s, warm-up "
            f"compile {time.time() - t1:.1f}s, port {port}"
        )
        async with ClientSession() as http:
            yield app, http, f"http://127.0.0.1:{port}/gordo/v0/{PROJECT}"
    finally:
        await runner.cleanup()


async def _check_serving_state(app, http, base, n_total, expect, n_devices):
    """Coverage, health and per-bucket provenance through the surfaces an
    operator reads. Nothing may have used a fallback."""
    async with http.get(f"{base}/models") as resp:
        cov = (await resp.json())["bank"]
    async with http.get(f"{base}/healthz") as resp:
        health = await resp.json()
        check(resp.status == 200, f"healthz HTTP {resp.status}: {health}")
    check(len(cov["banked"]) == n_total, f"banked {len(cov['banked'])}/{n_total}")
    check(cov["fallback"] == {}, f"fallback: {cov['fallback']}")
    check(cov["kernel"] == expect["kernel"], f"bank kernel {cov['kernel']!r}")
    check(cov["devices"] == n_devices, f"bank devices {cov['devices']}")
    check(health["status"] == "ok", f"healthz: {health}")
    check(health["bank_finalize_failures"] == {}, f"healthz: {health}")
    check(health["quarantined"] == {}, f"healthz: {health}")
    check(health["bank_warmup_error"] is None, f"healthz: {health}")
    rows = app["bank"].flops_stats()
    (lstm,) = [r for r in rows.values() if r["registry_type"] == "LSTMAutoEncoder"]
    check(
        (lstm["seq_layout"], lstm["seq_kernel"])
        == (expect["seq_layout"], expect["seq_kernel"]),
        f"LSTM bucket runs {lstm['seq_layout']}/{lstm['seq_kernel']}",
    )
    say(
        f"serve: banked={len(cov['banked'])}/{n_total} fallback={cov['fallback']} "
        f"kernel={cov['kernel']} n_buckets={cov['n_buckets']} "
        f"devices={cov['devices']} device={cov['device']}"
    )
    say(
        f"serve: healthz status={health['status']} "
        f"finalize_failures={health['bank_finalize_failures']} "
        f"quarantined={health['quarantined']} | LSTM bucket "
        f"seq_layout={lstm['seq_layout']} seq_kernel={lstm['seq_kernel']} "
        f"members={lstm['members']}"
    )
    return cov


async def _engine_stats(http, base) -> dict:
    async with http.get(f"{base}/stats") as resp:
        return (await resp.json())["bank_engine"]


async def _score_requests(http, base, trained, sizes, seed):
    """Step 3's requests: singles (dense + LSTM, JSON + tensor, first call
    and warm repeat) then one concurrent burst for ``burst`` different
    members, twice. Returns ``{(name, tag): arrays}`` for comparison."""
    answers = {}
    for name in (trained["dense"][0], trained["lstm"][0]):
        X = _request_rows(name, sizes, seed)
        for encoding in ("json", "tensor"):
            times = []
            for _ in range(2):
                t0 = time.time()
                answers[(name, encoding)] = await _post(http, base, name, X, encoding)
                times.append(time.time() - t0)
            say(
                f"score: single {name} [{encoding}] 200, first call "
                f"{times[0] * 1e3:.1f} ms, warm repeat {times[1] * 1e3:.1f} ms"
            )
    n_lstm = min(len(trained["lstm"]), sizes["burst"] // 4)
    members = (
        trained["dense"][: sizes["burst"] - n_lstm] + trained["lstm"][:n_lstm]
    )
    check(len(set(members)) == sizes["burst"], "burst needs distinct members")
    before = await _engine_stats(http, base)
    for attempt in ("first call (compiles the coalesced shapes)", "warm repeat"):
        t0 = time.time()
        got = await asyncio.gather(
            *[
                _post(http, base, n, _request_rows(n, sizes, seed), "tensor")
                for n in members
            ]
        )
        say(
            f"score: burst of {len(members)} requests for {len(members)} "
            f"members ({len(members) - n_lstm} dense + {n_lstm} LSTM), all 200: "
            f"{attempt} {time.time() - t0:.2f}s"
        )
    answers.update({(n, "burst"): a for n, a in zip(members, got)})
    after = await _engine_stats(http, base)
    check(
        after["max_batch_seen"] > 1 and after["batches"] > before["batches"],
        f"the engine never coalesced: {after}",
    )
    say(
        f"score: engine coalescing moved: batches {before['batches']} -> "
        f"{after['batches']}, requests {before['requests']} -> "
        f"{after['requests']}, max_batch_seen={after['max_batch_seen']}, "
        f"avg_batch={after.get('avg_batch')}"
    )
    return answers


def _compare_with_per_model(answers, model_dir, sizes, seed) -> None:
    t0 = time.time()
    worst, cache = {"dense": np.zeros(2), "lstm": np.zeros(2)}, {}
    for (name, tag), got in answers.items():
        if name not in cache:
            cache[name] = _reference(
                model_dir, name, _request_rows(name, sizes, seed)
            )
        fam = "dense" if name[0] == "d" else "lstm"
        worst[fam] = np.maximum(
            worst[fam], _compare(f"{name}[{tag}]", got, cache[name])
        )
    say(
        f"score: {len(answers)} responses agree with the per-model path "
        f"(serializer.load(dir).anomaly(X)) on {len(cache)} artifacts "
        f"({time.time() - t0:.1f}s incl. per-model compiles): "
        + "; ".join(
            f"{fam} max |diff| {a:.3g}, at most {share:.2%} of an array "
            f"outside rtol={RTOL} atol={ATOL}"
            for fam, (a, share) in worst.items()
        )
        + f" (allowed {FLIP_SHARE:.0%}, every element within "
        f"rtol={FLIP_RTOL:.3g} atol={FLIP_ATOL:.3g})"
    )


async def phase_serve_and_score(trained, sizes, seed, expect) -> None:
    import jax

    n_total = len(trained["dense"]) + len(trained["lstm"])
    async with _served(trained["model_dir"]) as (app, http, base):
        # default settings shard the bank over every device present
        await _check_serving_state(
            app, http, base, n_total, expect, n_devices=len(jax.devices())
        )
        answers = await _score_requests(http, base, trained, sizes, seed)
    _compare_with_per_model(answers, trained["model_dir"], sizes, seed)


# ------------------------------------------------------------------ #
# --four-chips: the sharded paths against a single-device bank
# ------------------------------------------------------------------ #


def _check_shards(bank, n_devices: int) -> None:
    """Each bucket's stacked state occupies ``n_devices`` different devices
    with 1/n of the (padded) member axis each — read from the arrays."""
    import jax

    for bucket in bank._buckets.values():
        for leaf in jax.tree.leaves((bucket.params, bucket.scalers)):
            shards = leaf.addressable_shards
            devices = {s.device for s in shards}
            check(
                len(devices) == n_devices,
                f"{bucket.label}: state on {len(devices)} device(s)",
            )
            check(
                all(s.data.shape[0] * n_devices == leaf.shape[0] for s in shards),
                f"{bucket.label}: uneven shards {[s.data.shape for s in shards]}",
            )
        say(
            f"four-chips: bucket {bucket.label}: {len(bucket.names)} members, "
            f"stacked state on {n_devices} distinct devices, "
            f"{bucket.shard_size} members each"
        )


async def phase_four_chips(trained, sizes, seed, expect, n_devices=4) -> None:
    """Serve the gang sharded over ``n_devices`` and hold it to a
    single-device bank built here on device 0."""
    from gordo_components_tpu.server.bank import ModelBank
    from gordo_components_tpu.utils.wire import ANOMALY_FRAME_NAMES

    check(
        trained["device"]["count"] == n_devices,
        f"the gang trained on {trained['device']}, not {n_devices} devices",
    )
    n_total = len(trained["dense"]) + len(trained["lstm"])
    async with _served(trained["model_dir"], devices=n_devices) as (app, http, base):
        cov = await _check_serving_state(
            app, http, base, n_total, expect, n_devices=n_devices
        )
        check(cov["device"]["count"] == n_devices, f"bank device {cov['device']}")
        sharded = app["bank"]
        _check_shards(sharded, n_devices)
        answers = await _score_requests(http, base, trained, sizes, seed)
        t0 = time.time()
        single = ModelBank.from_models(
            app["collection"].models, registry=False,
            bank_kernel=sharded.kernel_mode,
        )
        check(single.coverage()["device"]["count"] == 1, "single-device bank")
        members = sorted({n for n, _ in answers})
        differ, worst = [], np.zeros(2)
        for name in members:
            X = _request_rows(name, sizes, seed)
            a = single.score(name, X).to_arrays()
            b = sharded.score(name, X).to_arrays()
            if not all(np.array_equal(a[k], b[k]) for k in ANOMALY_FRAME_NAMES):
                differ.append(name)
            for tag in ("json", "tensor", "burst"):
                if (name, tag) in answers:
                    worst = np.maximum(
                        worst, _compare(f"{name}[{tag}]", answers[(name, tag)], a)
                    )
        check(
            not differ,
            "sharded and single-device banks differ bitwise at equal batch "
            f"composition for {differ}",
        )
        say(
            f"four-chips: sharded bank == single-device bank (device 0) "
            f"bitwise at equal batch composition on {len(members)} members; "
            f"{len(answers)} HTTP responses (coalesced, other batch "
            f"compositions) agree with it: max |diff| {worst[0]:.3g}, at most "
            f"{worst[1]:.2%} of an array outside rtol={RTOL} atol={ATOL} "
            f"({time.time() - t0:.1f}s)"
        )


# ------------------------------------------------------------------ #
# entry
# ------------------------------------------------------------------ #


def run(sizes: dict, seed: int, expect: dict, four_chips: bool = False) -> None:
    """All phases at ``sizes``; raises on the first failure. Importable so
    the CPU rehearsal (tests/test_chip_smoke.py) drives the same code at a
    tiny size with the kernels in interpret mode."""
    workdir = tempfile.mkdtemp(prefix="chip-smoke-")
    try:
        trained = phase_train(workdir, sizes, seed, expect)
        if four_chips:
            asyncio.run(phase_four_chips(trained, sizes, seed, expect))
        else:
            asyncio.run(phase_serve_and_score(trained, sizes, seed, expect))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--four-chips", action="store_true",
        help="run only the sharded paths (four-device trainer mesh, bank "
             "sharded over four chips) and the single-device bank they are "
             "compared with",
    )
    args = parser.parse_args(argv)

    import jax

    from gordo_components_tpu.native import native_available
    from gordo_components_tpu.ops.pallas_score import resolve_bank_kernel_mode
    from gordo_components_tpu.ops.seq_scan import (
        resolve_seq_kernel_mode,
        resolve_seq_layout,
    )
    from gordo_components_tpu.utils import resolve_compile_cache

    cache_dir = resolve_compile_cache()
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    need = 4 if args.four_chips else 1
    if device["platform"] != "tpu" or device["count"] < need:
        print(
            f"chip_smoke.py needs {need} TPU chip(s); JAX reports {device}",
            file=sys.stderr,
        )
        return 2
    t0 = time.time()
    entries_before = len(os.listdir(cache_dir))
    say(f"device: {device}")
    say(
        f"modes: bank_kernel={resolve_bank_kernel_mode()} "
        f"seq_layout={resolve_seq_layout()} seq_kernel={resolve_seq_kernel_mode()}"
    )
    say(
        "host ops: "
        + ("native (hostops.cpp)" if native_available() else "numpy")
    )
    say(f"compile cache: {cache_dir} ({entries_before} entries before the run)")
    try:
        run(FULL_SIZES, args.seed, TPU_EXPECT, four_chips=args.four_chips)
    except Exception as exc:
        import traceback

        traceback.print_exc()
        say(f"FAILED: {type(exc).__name__}: {exc}")
        return 1
    stats = devices[0].memory_stats() or {}
    say(f"memory: peak_bytes_in_use={stats.get('peak_bytes_in_use')} on {devices[0]}")
    say(
        f"compile cache: {cache_dir} holds {len(os.listdir(cache_dir))} entries "
        f"after the run ({entries_before} before)"
    )
    say(f"total: {time.time() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
