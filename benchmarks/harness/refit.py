"""Cells whose traffic is a job: refit one gang, again and again.

Each step of the window is one whole ``FleetTrainer.fit`` of the gang: a
fresh trainer built as ``build-fleet`` builds it, the same staged member
arrays. The window ends at the first fit boundary at or after
``--seconds`` and divides by the time really elapsed. Dataset staging and
artifact writing are outside it (PERF.md section 4 says why).
"""

import gc
import time
from typing import Dict, Sequence

import numpy as np

import families
from harness import adapter, check, common, spec, weights


NUMBERS = (
    "first_epoch_loss_gap", "loss_gap", "weight_change_gap", "input_scaler_gap",
    "error_scale_gap", "threshold_gap", "error_pass_gap",
)


def ladder_up(n: int, steps: Sequence[float], exact_up_to: int) -> int:
    """The smallest ``step x 2^k`` at or above ``n``; counts up to
    ``exact_up_to`` stay as they are. How the mix states the shapes a gang
    is padded to (``padding`` in ``traffic/refit.json``)."""
    if n <= exact_up_to:
        return max(1, n)
    p = 1
    while True:
        for step in steps:
            if int(p * step) >= n:
                return int(p * step)
        p *= 2


def _compare(config: dict, got: Dict[str, dict], want: dict, X: np.ndarray) -> Dict[str, float]:
    """The program's sampled members (``got``) against the reference's
    (``want``: the family's ``refit_sample`` output, stacked; ``X``: the
    members' rows).

    The fit itself (150 Adam steps) amplifies any rounding difference, so
    the numbers that follow it end to end (losses, the norm of the weights'
    change, scalers, thresholds) are held to limits that catch a broken or
    cheaper step, not the last bit. ``error_pass_gap`` recomputes the error
    scaler and the thresholds the fit returned from the weights the fit
    returned; it is printed for the record and held to no limit (PERF.md
    section 2)."""
    numbers = {k: 0.0 for k in NUMBERS}
    worst = lambda name, value: numbers.__setitem__(name, max(numbers[name], value))
    members = list(got.values())
    for s, member in enumerate(members):
        ref_loss = want["losses"][s].astype(np.float64)
        if member["losses"].shape != ref_loss.shape:
            worst("loss_gap", float("inf"))
            worst("first_epoch_loss_gap", float("inf"))
        else:
            gaps = np.abs(member["losses"] - ref_loss) / np.abs(ref_loss)
            worst("loss_gap", float(np.max(gaps)))
            worst("first_epoch_loss_gap", float(gaps[0]))
        w0 = {k: v[s] for k, v in want["w0"].items()}
        moved_ref = {k: want["w"][k][s] - w0[k] for k in w0}
        moved_got = {k: member["w"][k] - w0[k] for k in w0}
        worst("weight_change_gap", check.worst_leaf_norm_gap(moved_got, moved_ref))
        for k in ("in_shift", "in_scale"):
            worst("input_scaler_gap", check.sup_gap(member[k], want[k][s]))
        worst("error_scale_gap", check.sup_gap(member["err_scale"], want["err_scale"][s]))
        for k in ("feature_thresholds", "total_threshold"):
            worst("threshold_gap", check.sup_gap(member[k], want[k][s]))
    redone = families.load(config["family"], "refit").error_pass_sample(
        config, {k: np.stack([m["w"][k] for m in members]) for k in members[0]["w"]}, X
    )
    for s, member in enumerate(members):
        for k in ("err_scale", "feature_thresholds", "total_threshold"):
            worst("error_pass_gap", check.sup_gap(member[k], redone[k][s]))
    return numbers


class Gang:
    """One seed's staged gang: member arrays and the fit that the window
    (and the control script) repeats."""

    def __init__(self, cell: spec.Cell, seed: int):
        self.config, self.traffic = cell.config, cell.traffic
        self.seed = seed
        self.family_refit = families.load(self.config["family"], "refit")  # stops here if missing
        self.n_members = int(self.config["gang_members"])
        self.rows = int(self.traffic["rows"])
        # the shapes the mix says a gang is padded to: the reference's key
        # split and shuffle length come from here, never from the program
        pad, bs = self.traffic["padding"], int(self.config["batch_size"])
        self.padded_members = ladder_up(self.n_members, **pad["members"])
        self.padded_rows = ladder_up(-(-self.rows // bs), **pad["batches"]) * bs
        self.trainer_seed = seed % (2**31 - 1)
        self.names = [adapter.member_name(i) for i in range(self.n_members)]
        self.members = {
            name: weights.member_train_data(self.config, seed, i, self.rows)
            for i, name in enumerate(self.names)
        }
        n_check = min(int(self.traffic["check_members"]), self.n_members)
        self.sample = sorted(
            weights.rng_for(seed, weights.SAMPLE)
            .choice(self.n_members, n_check, replace=False).tolist()
        )

    def fit(self):
        trainer, hparams = adapter.gang_trainer(self.config, self.trainer_seed, self.names)
        t0 = time.monotonic()
        with common.annotate("fit"):
            models = trainer.fit(self.members, member_hparams=hparams)
        return models, trainer.last_stats, time.monotonic() - t0

    def sampled(self, models) -> Dict[str, dict]:
        return {
            self.names[i]: adapter.member_arrays(self.config, models[self.names[i]])
            for i in self.sample
        }

    @property
    def sample_rows(self) -> np.ndarray:
        return np.stack([self.members[self.names[i]] for i in self.sample])

    def check_padding(self, bucket: dict) -> None:
        """The program has to have padded the gang as the mix states: the
        reference draws its per-member keys and its shuffle from those
        sizes, so a trainer that pads otherwise cannot be followed and the
        mix has to be restated by a benchmark PR."""
        got = (int(bucket["padded_members"]), int(bucket["padded_items"]))
        if got != (self.padded_members, self.padded_rows):
            raise RuntimeError(
                f"the trainer padded the gang to (members, rows) {got}; the mix's padding "
                f"rule gives {(self.padded_members, self.padded_rows)}"
            )

    def reference(self, **how):
        """The plain reference over the sampled members (``how``: dtype,
        precision or fault, for the control script)."""
        return self.family_refit.refit_sample(
            self.config, self.trainer_seed, self.padded_members, self.sample,
            self.sample_rows, self.padded_rows, **how,
        )


def reference_as_program(want: dict) -> Dict[str, dict]:
    """A reference run in the shape ``_compare`` takes for the program's
    members: how the control and the planted faults are read."""
    n = len(want["losses"])
    return {
        str(s): dict(
            {k: want[k][s] for k in want if k not in ("w", "w0", "losses")},
            w={k: v[s] for k, v in want["w"].items()},
            losses=want["losses"][s].astype(np.float64),
        )
        for s in range(n)
    }


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool, t_start: float,
        on_tpu: bool = True) -> dict:
    gang = Gang(cell, seed)
    # set-up: one whole fit compiles (or loads) every program the window uses
    models, stats, warm_wall = gang.fit()
    decisions = adapter.check_training_decisions(
        stats, adapter.tpu_decisions(cell.config, "train") if on_tpu else None
    )
    gang.check_padding(stats["buckets"][0])
    print(f"set-up fit {warm_wall:.2f}s, decisions {decisions}", flush=True)
    del models
    work = common.work_dir()
    window = common.TracedWindow(work) if traced else None
    setup_s = time.time() - t_start

    fits, t0 = [], time.monotonic()
    while True:
        if window is not None and not fits:
            window.start()
        models, stats, wall = gang.fit()
        if window is not None and not fits:
            window.stop()
        fits.append({"wall_s": wall, "epoch_seconds": stats["buckets"][0]["epoch_seconds"]})
        elapsed = time.monotonic() - t0
        if elapsed >= seconds:
            break
    memory_peak = common.memory_peak_bytes()
    bucket = stats["buckets"][0]
    print(f"window: {len(fits)} fits in {elapsed:.2f}s: "
          f"{[round(f['wall_s'], 2) for f in fits]}", flush=True)

    # the members the last fit of the window returned, a sample drawn from the seed
    got = gang.sampled(models)
    del models
    gc.collect()
    t_ref = time.monotonic()
    checks = check.verdict(
        _compare(cell.config, got, gang.reference(), gang.sample_rows), cell.limits
    )
    print(f"reference over {len(got)} members: {time.monotonic() - t_ref:.2f}s", flush=True)

    done = len(fits) * gang.n_members
    obs = {
        "config": cell.config, "traffic": cell.traffic, "fits": fits, "window_s": elapsed,
        "members_done": done, "gang_members": gang.n_members, "rows": gang.rows,
        "padded_rows": int(bucket["padded_rows"]),
    }
    if traced:
        obs["trace"] = window.reduce()
        obs["traced_window_s"] = window.window_s
        obs["peaks"] = spec.peaks_for(common.device_block()["kind"]) if obs["trace"] else None
    common.remove(work)
    values = {"train_members_per_s": done / elapsed, "setup_s": setup_s}
    return common.emit(cell, traced, values, obs, len(fits), 0, checks, memory_peak)
