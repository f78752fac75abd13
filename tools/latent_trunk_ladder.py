#!/usr/bin/env python3
"""Device time of the latent-attention trunk's pieces at a week-long
request (run on the chip; no test and no benchmark runs this):

    python3 tools/latent_trunk_ladder.py [--rows 10240] [--kernel 512x4,512x8] [--layers]
    python3 tools/latent_trunk_ladder.py --kernel "" --experts 1024,2048,2560 [--config ...]
    python3 tools/latent_trunk_ladder.py --config benchmarks/configs/glm52_trunk300.json \
        --kernel 1024x2,1024x1,512x4,512x2,1024x4@64 --layers

``--kernel``: ``ops/latent_attention.py``'s kernel alone at the published
head sizes (64 heads of 128 + 64 | 128, or ``--config``'s) for each ``tile
x heads-a-step`` (``@MB``: under that VMEM limit), wall time around
``block_until_ready`` over ``--repeats`` calls, beside the share of the
chip's peak its causal operations come to; under ``--config`` with
``indexer_types`` the kernel runs under a selection of ``index_topk`` keys a
query (random ones: the time does not depend on which) and again without
one. ``--layers``: every kind of layer program the trunk has
(``LatentMoEDecoder.layer``: dense and routed with 12 of 192 experts held,
or ``--config``'s kinds, the selection handed from one to the next) on
random weights, the same way. It fails where JAX finds no TPU unless
``--interpret`` (a rehearsal of the script at a tiny size: its times say
nothing). ``--experts``: ``ops/moe.py::expert_layer`` alone over one run of a
request's rows (``_rows_a_run``) at the configuration's widths and held
range, for each block of sorted pairs (``moe._BLOCK_PAIRS``) at three
loads: ``even`` (a row's experts uniform over the router's width: 1/16 of
the pairs held), ``skew`` (the benchmark's: 6.5% of the pairs held, two
thirds of them on one held expert) and ``all`` (every pair on a held
expert). The routing is planted in the rows' first E features, which an
identity router reads. A checkout whose ``moe`` has no blocks (a parent
commit: copy this file into its ``tools/``) is timed once a load.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

YARN = dict(type="yarn", factor=32, original_max_position_embeddings=4096, beta_fast=32,
            beta_slow=1, mscale=1, mscale_all_dim=1)
PEAK = 197e12


def timed(fn, *args, repeats: int):
    import jax

    jax.block_until_ready(fn(*args))  # compiles
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def planted_rows(module, rows: int, load: str, seed: int = 0):
    """``(h, router)`` whose routing is the load's: a row's first E
    features are 4 on its 8 experts and -4 elsewhere, the router reads
    them. Held experts are chosen one by one with the load's probability,
    the rest of a row's 8 among absent experts of the three groups after
    the held range's (so a group limit keeps them)."""
    import numpy as np

    E, k, held = module.n_routed_experts, module.num_experts_per_tok, module.held
    rng = np.random.default_rng(seed)
    if load == "all":
        p = None
    elif load == "even":
        p = np.full(held, k / E)
    else:  # skew: 6.5% of the pairs held, two thirds of them on the first held expert
        per_row = 0.065 * k
        p = np.full(held, per_row / 3 / (held - 1))
        p[0] = per_row * 2 / 3
    size = E // module.n_group
    absent = np.arange(max(size, held), max(size, held) + 3 * size) if module.n_group > 1 else np.arange(held, E)
    scores = np.full((rows, E), -1.0)  # a row's k highest are its experts
    if p is None:
        scores[:, :held] = rng.random((rows, held))
    else:
        scores[:, absent] = rng.random((rows, absent.size))
        scores[:, :held] = np.where(rng.random((rows, held)) < p, 2.0, -1.0)
    chosen = np.argsort(-scores, axis=1)[:, :k]
    code = np.full((rows, E), -4.0, np.float32)
    np.put_along_axis(code, chosen, 4.0, axis=1)
    code += rng.normal(scale=0.01, size=code.shape).astype(np.float32)  # no ties
    h = rng.normal(size=(rows, module.hidden_size)).astype(np.float32)
    h[:, :E] = code
    router = np.zeros((module.hidden_size, E), np.float32)
    router[np.arange(E), np.arange(E)] = 1.0
    return h, router


def experts_ladder(module, T: int, blocks, args, say) -> None:
    import jax
    import jax.numpy as jnp

    from gordo_components_tpu.ops import moe

    rows, k = module._rows_a_run(T), module.num_experts_per_tok
    shapes = module.layer_shapes(module.num_hidden_layers - 1)
    key = jax.random.PRNGKey(3)
    params = {
        name: (jax.random.uniform(jax.random.fold_in(key, i), shapes[name], jnp.float32, -1, 1)
               * (3.0 / shapes[name][-2]) ** 0.5).astype(jnp.bfloat16)
        for i, name in enumerate(("gate", "up", "down"))
    }
    if "router_bias" in shapes:
        params["router_bias"] = jnp.zeros(shapes["router_bias"], jnp.float32)
    routing = dict(expert_offset=module.expert_offset, scoring=module.scoring_func,
                   n_group=module.n_group, topk_group=module.topk_group,
                   scale=module.routed_scaling_factor)
    valid = jnp.ones((rows,), bool)
    calls = 1 if args.interpret else 10
    if not hasattr(moe, "_BLOCK_PAIRS"):
        blocks = [None]
    for load in ("even", "skew", "all"):
        h, router = planted_rows(module, rows, load)
        h, w = jnp.asarray(h), {**params, "router": jnp.asarray(router)}
        for block in blocks:
            if block is not None:
                moe._BLOCK_PAIRS = block
            fn = jax.jit(lambda h, w: moe.expert_layer(h, w, k, valid, args.interpret, **routing))

            def several(h, w):
                return [fn(h, w)[0] for _ in range(calls)]

            seconds = timed(several, h, w, repeats=args.repeats) / calls
            seen = fn(h, w)
            say({"experts": load, "block": block, "rows": rows, "pairs": rows * k,
                 "held": module.held, "hidden": module.hidden_size, "ms": 1e3 * seconds,
                 "held_pairs": int(seen[2].sum()), "busiest": int(seen[2].max()),
                 "blocks": int(seen[3]) if len(seen) > 3 else None})


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rows", type=int, default=10240)
    parser.add_argument("--kernel", default="1024x2,512x4")
    parser.add_argument("--layers", action="store_true")
    parser.add_argument("--experts", default="", help="blocks of sorted pairs, e.g. 1024,2048,2560")
    parser.add_argument("--repeats", type=int, default=3)
    parser.add_argument("--interpret", action="store_true")
    parser.add_argument("--out", default="chiprun_out/latent_trunk_ladder.jsonl")
    parser.add_argument("--config", default=None, help="a benchmark configuration file: its TrunkForecast sizes")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from gordo_components_tpu.models.factories.trunk import LatentMoEDecoder
    from gordo_components_tpu.ops import latent_attention as la

    if jax.devices()[0].platform != "tpu" and not args.interpret:
        print(f"no TPU here ({jax.devices()[0]}): a CPU timing of this says nothing", file=sys.stderr)
        return 2
    small = dict(hidden_size=64, num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
                 qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
                 moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=4, n_group=4,
                 topk_group=2, experts_held=4, chunk_size=16) if args.interpret else {}
    sizes = dict(num_hidden_layers=2, rope_scaling=YARN, **(small or dict(experts_held=12)))
    if args.config:
        with open(args.config) as fh:
            (_, det), = json.load(fh)["model"].items()
        (_, pipe), = det["base_estimator"].items()
        (_, sizes), = pipe["steps"][-1].items()
        sizes = {k: v for k, v in sizes.items() if k not in ("kind", "trunk")}
        if args.interpret:
            sizes.update(small, index_n_heads=4, index_head_dim=16, index_topk=24)
        if sizes.get("indexer_types"):
            sizes["indexer_types"] = tuple(sizes["indexer_types"])
    module = LatentMoEDecoder(n_features=300, **sizes)
    T, H = args.rows, module.num_attention_heads
    nope, rope_dim, dv = module.qk_nope_head_dim, module.qk_rope_head_dim, module.v_head_dim
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    out = open(args.out, "a")

    def say(row):
        print(json.dumps(row), flush=True)
        out.write(json.dumps(row) + "\n")
        out.flush()

    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    draw = lambda k, shape: jax.random.normal(k, shape, jnp.float32).astype(jnp.bfloat16)
    flops = 2.0 * H * (nope + rope_dim + dv) * T * (T + 1) / 2
    selections = [None]
    if module.indexer_types:  # index_topk random keys a query, its own among them: causal, none empty
        scores = jnp.where(jnp.tril(jnp.ones((T, T), bool)), jax.random.uniform(keys[7], (T, T)), -1.0)
        scores = scores.at[jnp.arange(T), jnp.arange(T)].set(2.0)
        kth = jax.lax.top_k(scores, min(module.index_topk, T))[0][:, -1:]
        selections.insert(0, ((scores >= kth) & (scores >= 0)).astype(jnp.int8))
        del scores, kth
    for spec in filter(None, args.kernel.split(",")):
        spec, _, vmem = spec.partition("@")
        tile, heads = (int(v) for v in spec.split("x"))
        la._TILES = ((tile, heads),)
        la._VMEM_LIMIT_BYTES = int(vmem or 32) * 1024 * 1024
        operands = (draw(keys[0], (H, T, nope)), draw(keys[1], (H, T, rope_dim)),
                    draw(keys[2], (H, T, nope)), draw(keys[3], (T, rope_dim)), draw(keys[4], (H, T, dv)))
        for selection in selections:
            fn = jax.jit(lambda *a: la.latent_attention(
                *a[:5], granule=tile, interpret=args.interpret, selection=a[5] if len(a) > 5 else None))
            row = {"kernel": spec, "vmem_mb": int(vmem or 32), "rows": T, "selected": selection is not None}
            try:
                seconds = timed(fn, *operands, *(() if selection is None else (selection,)),
                                repeats=args.repeats)
                say({**row, "ms": 1e3 * seconds, "share_of_peak_causal_operations": flops / seconds / PEAK})
            except Exception as exc:  # a tile the chip's lowering or its VMEM refuses
                say({**row, "refused": f"{type(exc).__name__}: {str(exc)[:300]}"})
    if args.experts:
        experts_ladder(module, T, [int(v) for v in args.experts.split(",")], args, say)
    if args.layers:
        x = jax.random.normal(keys[5], (1, T, module.hidden_size), jnp.float32)
        n_valid = jnp.asarray([T - 160], jnp.int32)
        fn = jax.jit(lambda w, x, n, s: module.layer(w, x, n, s, interpret=args.interpret))
        kinds, selection = {}, None
        for index in range(module.num_hidden_layers):  # one of each kind, in the order the trunk has them
            shapes = module.layer_shapes(index)
            kind = ("routed" if "router" in shapes else "dense") + (
                "" if not module.indexer_types else "+" + module.indexer_types[index])
            if kind in kinds:
                continue
            kinds[kind] = index
            layer = {
                name: ((jnp.zeros if name.endswith("_bias") else jnp.ones)(shape, jnp.float32)
                       if len(shape) == 1 else
                       (jax.random.uniform(jax.random.fold_in(keys[6], i), shape, jnp.float32, -1, 1)
                        * (3.0 / shape[-2]) ** 0.5).astype(jnp.bfloat16))
                for i, (name, shape) in enumerate(shapes.items())
            }
            seconds = timed(fn, layer, x, n_valid, selection, repeats=args.repeats)
            _, selection, seen = fn(layer, x, n_valid, selection)
            say({"layer": kind, "rows": T, "ms": 1e3 * seconds,
                 "held_tokens": [int(v) for v in seen["held_tokens"]] if "held_tokens" in seen else None,
                 "selections": [int(v) for v in seen["selections"]] if "selections" in seen else None,
                 "memory_peak_gb": (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0) / 1e9})
            del layer
    return 0


if __name__ == "__main__":
    sys.exit(main())
