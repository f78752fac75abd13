"""What framing a tensor answer costs the event loop, and what its client
sees of it, three ways over one loopback ``aiohttp`` server: ``stored``
(``pack_frames`` as it stood before PR 34, kept here as the plain
baseline: a zero-filled ``bytearray``, every array copied in, then
``bytes(buf)``), ``join`` (today's ``pack_frames``: the segments joined
once) and ``segments`` (``views.TensorBody``: what the tensor views
return, payloads written by reference). Shapes are ``rows x tags`` of an
anomaly answer; ``s`` after a shape adds a shared trunk's selections
(``10080x300s`` is ``keye_trunk300.week``'s answer, 50.4 MB; ``256x300``
is ``dense300.live``'s). The client is a child process (no shared GIL),
requests one at a time, the ways taking turns; ``encode`` is what the
views' ``encode`` span times, ``client`` is request sent -> whole body
read. On the chip's host: ``python3 tools/encode_ladder.py`` (PERF.md
section 6, PR 34). The three bodies are compared byte for byte."""

import argparse
import asyncio
import hashlib
import json
import os
import statistics
import struct
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

WAYS = ("stored", "join", "segments")
WARM = 2


def stored_pack(frames) -> bytes:
    """``utils.wire.pack_frames`` before PR 34 (its checks left out)."""
    u64 = struct.Struct("<Q")
    staged = []
    total = 6
    for name, arr in frames:
        arr = np.ascontiguousarray(arr)
        name_b, dtype_b = name.encode("utf-8"), arr.dtype.str.encode("ascii")
        staged.append((name_b, dtype_b, arr))
        total += 1 + len(name_b) + 1 + len(dtype_b) + 1 + 8 * arr.ndim + 8 + arr.nbytes
    buf = bytearray(total)
    mv = memoryview(buf)
    buf[:4] = b"GTNS"
    buf[4] = 1
    buf[5] = len(staged)
    pos = 6
    for name_b, dtype_b, arr in staged:
        for field in (name_b, dtype_b):
            buf[pos] = len(field)
            buf[pos + 1 : pos + 1 + len(field)] = field
            pos += 1 + len(field)
        buf[pos] = arr.ndim
        pos += 1
        for dim in (*arr.shape, arr.nbytes):
            u64.pack_into(buf, pos, dim)
            pos += 8
        if arr.nbytes:
            mv[pos : pos + arr.nbytes] = memoryview(arr).cast("B")
            pos += arr.nbytes
    return bytes(buf)


def answer_frames(shape: str, rng: np.random.Generator):
    from gordo_components_tpu.server.model_io import anomaly_frames
    from gordo_components_tpu.utils.wire import ANOMALY_FRAME_NAMES

    rows, tags = (int(v) for v in shape.rstrip("s").split("x"))
    arrays = {
        name: rng.random((rows, tags) if "tag" in name or "model" in name else rows, dtype=np.float32)
        for name in ANOMALY_FRAME_NAMES
    }
    if shape.endswith("s"):  # six layers: every row's 8 experts, every 64th row's keys as bits
        padded = -(-rows // 1024) * 1024
        arrays["expert-selection"] = rng.integers(0, 128, (6, rows, 8), dtype=np.uint8)
        arrays["key-selection"] = rng.integers(0, 256, (6, padded // 64, padded // 8), dtype=np.uint8)
    return anomaly_frames([f"tag-{i}" for i in range(tags)], arrays, 0)


async def client(base: str, reps: int) -> dict:
    from aiohttp import ClientSession

    out = {way: {"ms": []} for way in WAYS}
    async with ClientSession() as http:
        for rep in range(WARM + reps):
            for way in WAYS:
                t0 = time.monotonic()
                async with http.get(f"{base}/{way}") as resp:
                    raw = await resp.read()
                    dt = time.monotonic() - t0
                    if rep >= WARM:
                        out[way]["ms"].append(1e3 * dt)
                    out[way].update(
                        sha=hashlib.sha256(raw).hexdigest(), bytes=len(raw),
                        content_length=resp.headers.get("Content-Length"),
                        transfer_encoding=resp.headers.get("Transfer-Encoding"),
                    )
    return out


async def ladder(shape: str, reps: int) -> dict:
    from aiohttp import web

    from gordo_components_tpu.server.views import TensorBody
    from gordo_components_tpu.utils.wire import TENSOR_CONTENT_TYPE, pack_frames

    frames = answer_frames(shape, np.random.default_rng(0))
    bodies = {"stored": stored_pack, "join": pack_frames, "segments": TensorBody}
    encode_s = {way: [] for way in WAYS}

    async def handle(request):
        way = request.match_info["way"]
        t0 = time.monotonic()
        body = bodies[way](frames)
        encode_s[way].append(time.monotonic() - t0)
        return web.Response(body=body, content_type=TENSOR_CONTENT_TYPE)

    app = web.Application()
    app.router.add_get("/{way}", handle)
    runner = web.AppRunner(app)
    await runner.setup()
    try:
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        base = f"http://127.0.0.1:{runner.addresses[0][1]}"
        child = await asyncio.create_subprocess_exec(
            sys.executable, os.path.abspath(__file__), "--client", base, "--reps", str(reps),
            stdout=asyncio.subprocess.PIPE,
        )
        stdout, _ = await child.communicate()
        if child.returncode:
            raise RuntimeError(f"the client exited with {child.returncode}")
    finally:
        await runner.cleanup()
    seen = json.loads(stdout)
    want = hashlib.sha256(stored_pack(frames)).hexdigest()
    out = {}
    for way in WAYS:
        got = seen[way]
        if got["sha"] != want or got["content_length"] != str(got["bytes"]) or got["transfer_encoding"]:
            raise RuntimeError(f"{shape} {way}: not the stored body, or not sent under its length: {got}")
        out[way] = {
            "body_bytes": got["bytes"],
            "encode_ms": round(1e3 * statistics.median(encode_s[way][WARM:]), 3),
            "client_ms": round(statistics.median(got["ms"]), 3),
            "client_all_ms": [round(v, 1) for v in got["ms"]],
        }
        print(shape, way, json.dumps(out[way]), flush=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="*", default=["256x300", "10080x300s"])
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--client", help="(the child's side) the server's base URL")
    args = ap.parse_args(argv)
    if args.client:
        print(json.dumps(asyncio.run(client(args.client, args.reps))))
        return
    out = {shape: asyncio.run(ladder(shape, args.reps)) for shape in args.shapes}
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/encode_ladder.json", "w") as fh:
        json.dump(out, fh, indent=1)


if __name__ == "__main__":
    main()
