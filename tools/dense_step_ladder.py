#!/usr/bin/env python3
"""The dense gang's fused training step (ops/dense_step.py) against
``vmap(epoch)`` on the chip, program against program (no test and no
benchmark runs this on a chip; tests/test_dense_step.py rehearses it):

    PYTHONPATH=. python3 tools/dense_step_ladder.py --out chiprun_out/step_ladder.jsonl

Two questions, one JSON line an answer.

**Where the kernel starts to pay** (``--rungs tags x members,...``). For
each rung it builds both epoch programs of a default detector
(``feedforward_hourglass``, float32, Adam, MSE) of that many tags through
``parallel/fleet._BucketPrograms``, the fused one WHATEVER
``dense_step.resolve`` would say of it, and times ``run_epoch`` on the same
gang (``--rows`` rows a member, batches of ``--batch``): the median of
``--repeats`` dispatches, host clock around ``block_until_ready`` (an epoch
is 16 steps: milliseconds, against 0.1 of dispatch). The crossover it finds
is what ``dense_step.NARROWEST_STATE_BYTES`` states.

**Whether a ragged gang trains the same** (``--ragged tags x members``).
Members of five different row counts (so that in most steps some members
have rows and others none), every seventh frozen as early stopping freezes
them, the gang opening with a frozen member and closing with a run of them:
one epoch of both programs from the same state. Frozen members must come
back bitwise as they went in (rng included) from both; both Adam counts
must be the number of batches in which the member had rows; parameters and
moments of the others are compared leaf by leaf (the norm of the difference
over the norm of the vmapped program's change). Exits 1 where any of that
fails (``--limit`` for the last).

It fails where ``dense_step.MODES`` has no entry for the backend (a CPU
timing of either program says nothing); ``--interpret`` is the rehearsal.
"""

import argparse
import json
import statistics
import sys
import time

import numpy as np


def _pairs(text):
    return [tuple(int(n) for n in item.split("x")) for item in text.split(",") if item]


def programs(tags: int, batch: int, mode: str):
    """``(module, fused, vmapped)``: both epoch programs of one bucket."""
    from gordo_components_tpu.models.register import lookup_factory
    from gordo_components_tpu.parallel.fleet import _BucketPrograms

    module = lookup_factory("AutoEncoder", "feedforward_hourglass")(
        tags, compute_dtype="float32"
    )
    fused = _BucketPrograms(module, "adam", 1e-3, batch, fused_step=(mode, None))
    vmapped = _BucketPrograms(module, "adam", 1e-3, batch)
    assert (fused.layout, vmapped.layout) == ("fused_step", "legacy")
    return module, fused, vmapped


def gang(progs, tags: int, members: int, rows, rows_pad: int, seed: int):
    """``(make_states, X, mask)``: ``rows`` real rows a member (a number,
    or one a member), padded to ``rows_pad``."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed)
    X = jax.random.uniform(key, (members, rows_pad, tags), jnp.float32)
    mask = (jnp.arange(rows_pad)[None, :] < jnp.asarray(rows).reshape(-1, 1)).astype(jnp.float32)
    mask = jnp.broadcast_to(mask, (members, rows_pad))

    def make_states():  # run_epoch donates its state: a fresh one a program
        return progs.init_stacked(
            jax.random.split(jax.random.fold_in(key, 1), members), jnp.zeros((members, tags))
        )

    return make_states, X, mask


def epoch_ms(progs, states, X, mask, active, repeats: int) -> float:
    import jax

    states, losses = progs.run_epoch(states, X, mask, active)  # compiles
    jax.block_until_ready(losses)
    seconds = []
    for _ in range(repeats):
        start = time.perf_counter()
        states, losses = progs.run_epoch(states, X, mask, active)
        jax.block_until_ready((states, losses))
        seconds.append(time.perf_counter() - start)
    return 1e3 * statistics.median(seconds)


def rung(tags, members, args, mode) -> dict:
    import jax.numpy as jnp

    from gordo_components_tpu.ops import dense_step

    module, fused, vmapped = programs(tags, args.batch, mode)
    rows_pad = -(-args.rows // args.batch) * args.batch
    make_states, X, mask = gang(fused, tags, members, args.rows, rows_pad, args.seed)
    active = jnp.ones((members,), jnp.float32)
    widths, _ = dense_step.chain_of(module)
    real, tiled = dense_step.state_bytes(widths)
    ms = {
        name: epoch_ms(progs, make_states(), X, mask, active, args.repeats)
        for name, progs in (("fused", fused), ("vmapped", vmapped))
    }
    steps = rows_pad // args.batch
    return {
        "rung": f"{tags}x{members}", "state_bytes": real, "state_bytes_tiled": tiled,
        "steps": steps, "fused_epoch_ms": ms["fused"], "vmapped_epoch_ms": ms["vmapped"],
        "fused_us_a_member_step": 1e3 * ms["fused"] / (steps * members),
        "vmapped_us_a_member_step": 1e3 * ms["vmapped"] / (steps * members),
        "fused_over_vmapped": ms["fused"] / ms["vmapped"],
        "resolve": dense_step.resolve(module, "mse", "adam", None, args.batch, "tpu"),
    }


def ragged(tags, members, args, mode) -> dict:
    import jax
    import jax.numpy as jnp

    _, fused, vmapped = programs(tags, args.batch, mode)
    rows_pad = -(-args.rows // args.batch) * args.batch
    ladder = np.array([args.rows, (2 * args.rows) // 3, args.rows // 2, args.rows // 6, 3])
    rows = ladder[np.arange(members) % len(ladder)]
    frozen = np.arange(members) % 7 == 0  # member 0 among them
    frozen[-3:] = True
    make_states, X, mask = gang(fused, tags, members, rows, rows_pad, args.seed)
    active = jnp.asarray(~frozen, jnp.float32)
    before = jax.tree.map(np.asarray, make_states())
    out = {}
    for name, progs in (("fused", fused), ("vmapped", vmapped)):
        states, losses = progs.run_epoch(make_states(), X, mask, active)
        out[name] = jax.tree.map(np.asarray, (states, losses))
    (got, got_loss), (want, want_loss) = out["fused"], out["vmapped"]

    stepped = np.where(frozen, 0, -(-rows // args.batch))
    leaves = lambda tree: jax.tree.leaves_with_path(tree)
    frozen_bitwise = all(
        np.array_equal(a[frozen], b[frozen]) and np.array_equal(c[frozen], b[frozen])
        for (_, a), (_, b), (_, c) in zip(leaves(got), leaves(before), leaves(want))
    )
    counts_right = all(
        np.array_equal(np.asarray(count), stepped)
        for state in (got, want)
        for count in (state.opt_state.count, state.opt_state.inner_state[0].count)
    )
    adam_got, adam_want, adam_before = (
        s.opt_state.inner_state[0] for s in (got, want, before)
    )
    gaps = {}
    for kind, a, b, c in (
        ("params", got.params, want.params, before.params),
        ("mu", adam_got.mu, adam_want.mu, adam_before.mu),
        ("nu", adam_got.nu, adam_want.nu, adam_before.nu),
    ):
        gaps[kind] = max(
            float(np.linalg.norm((x - y)[~frozen]) / np.linalg.norm((y - z)[~frozen]))
            for (_, x), (_, y), (_, z) in zip(leaves(a), leaves(b), leaves(c))
        )
    loss_gap = float(np.max(np.abs(got_loss[~frozen] / want_loss[~frozen] - 1)))
    result = {
        "ragged": f"{tags}x{members}", "rows": sorted(set(int(r) for r in rows)),
        "frozen": int(frozen.sum()), "steps_a_member": sorted(set(int(s) for s in stepped)),
        "frozen_bitwise": bool(frozen_bitwise), "counts_right": bool(counts_right),
        "frozen_losses_nan": bool(np.isnan(got_loss[frozen]).all() and np.isnan(want_loss[frozen]).all()),
        "rng_same": bool(np.array_equal(got.rng, want.rng)),
        "change_gap": gaps, "loss_gap": loss_gap, "limit": args.limit,
    }
    result["ok"] = bool(
        frozen_bitwise and counts_right and result["frozen_losses_nan"] and result["rng_same"]
        and max(gaps.values()) <= args.limit and loss_gap <= args.limit
    )
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rungs", type=_pairs,
                        default=_pairs("10x4096,10x1024,30x4096,60x2048,100x2048,150x1024,200x1024,300x640"))
    parser.add_argument("--ragged", type=_pairs, default=_pairs("300x320"))
    parser.add_argument("--rows", type=int, default=1440)
    parser.add_argument("--batch", type=int, default=100)
    parser.add_argument("--repeats", type=int, default=5)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--limit", type=float, default=0.02,
                        help="largest gap of a ragged gang's leaves and losses")
    parser.add_argument("--interpret", action="store_true",
                        help="rehearsal on a backend the kernel does not run on")
    parser.add_argument("--out", default=None, help="append the lines to this file too")
    args = parser.parse_args(argv)

    import jax

    from gordo_components_tpu.ops import dense_step

    mode = "interpret" if args.interpret else dense_step.MODES[jax.default_backend()]
    device = jax.devices()[0]
    ok = True
    for kind, pairs in ((rung, args.rungs), (ragged, args.ragged)):
        for tags, members in pairs:
            line = kind(tags, members, args, mode)
            line.update(platform=device.platform, device_kind=device.device_kind, mode=mode)
            ok = ok and line.get("ok", True)
            print(json.dumps(line), flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(line) + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
