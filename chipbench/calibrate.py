"""Readings that a cell's correctness limits are set from, on the chip.

    python3 chipbench/calibrate.py --workload granite-3-2b.decode \
        --seeds 11,12,13 [--out calib.jsonl]

Sets the cell up once, then for each seed: weights from that seed, as
many rounds of the cell's traffic through the timed path as it takes to
sample as many requests as a run does, that sample, and readings over it:

- ``max_logit_gap`` and ``mean_logit_gap``: the program's served tokens
  against the float32 reference (the lower reading of each is the largest
  over the seeds);
- ``control_max_gap`` and ``control_mean_gap``: the same for the tokens
  that the reference with float8 weights puts first (the upper reading of
  each is the smallest).

The benchmark's own runs never run the control. One JSON line per seed.

    python3 chipbench/calibrate.py --workload granite-3-2b.decode \
        --set-limits calib.jsonl

sets the cell's limits in ``workloads/<cell>.json`` from those lines, by
one rule (`limit`), and keeps the readings beside them."""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


NUMBERS = {"max_logit_gap": "control_max_gap",
           "mean_logit_gap": "control_mean_gap"}


def limit(lower: float, upper: float) -> float | None:
    """A number's limit from its lower reading (the largest a sound program
    gave) and its upper one (the smallest the control gave): none where the
    control reads under three times the lower; otherwise 60 % of the way
    from lower to upper on a log scale, so that there is room on both
    sides and more of it above the lower (fresh seeds read higher than the
    calibration's did). Three significant digits."""
    if upper < 3 * lower:
        return None
    return float(f"{lower ** 0.4 * upper ** 0.6:.3g}")


def set_limits(cell_name: str, jsonl: Path) -> dict:
    from chipbench import spec

    lines = [json.loads(x) for x in Path(jsonl).read_text().splitlines()
             if x.strip()]
    lines = [x for x in lines if x["cell"] == cell_name]
    if len(lines) < 12:
        raise ValueError(f"{len(lines)} seeds of {cell_name}; need 12")
    path = spec.ROOT / "chipbench" / "workloads" / f"{cell_name}.json"
    check = json.loads(path.read_text())
    readings = {"seeds": [x["seed"] for x in lines]}
    limits = {}
    for number, control in NUMBERS.items():
        lo = max(x[number] for x in lines)
        hi = min(x[control] for x in lines)
        lim = limit(lo, hi)
        readings[number] = {"lower": lo, "upper": hi, "limit": lim}
        if lim is not None:
            limits[number] = lim
    if not limits:
        raise ValueError(f"no number of {cell_name} separates the control")
    check["limits"], check["readings"] = limits, readings
    path.write_text(json.dumps(check, indent=2) + "\n")
    return check


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds")
    ap.add_argument("--out", default=None)
    ap.add_argument("--set-limits", type=Path, default=None)
    args = ap.parse_args(argv)
    if args.set_limits:
        print(json.dumps(set_limits(args.workload, args.set_limits)))
        return 0

    from chipbench import run
    from chipbench import spec

    cell = spec.load_cell(args.workload)
    jax = run.configure_jax()
    try:
        device = run.find_chips(jax, cell.chips)[0]
    except run.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    from chipbench import check, harness, weights
    from repro.configs import get_config
    from repro.models import init_params

    ref = spec.reference(cell.config["reference"])
    dims = ref.dims(cell.config)
    cfg = get_config(cell.config["arch"])
    run.check_program_config(cfg, dims)
    seeds = [int(s) for s in args.seeds.split(",")]
    server = harness.set_up(cfg, cell.traffic, seeds[0], dims["vocab"],
                            device)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    make = weights.maker(shapes)
    B, k = cell.traffic["batch"], cell.check["sample_requests"]
    n_rounds = -(-k // B)      # enough rounds to sample as many as a run
    out = open(args.out, "a") if args.out else None
    try:
        for seed in seeds:
            t0 = time.perf_counter()
            server.params = None
            server.params = make(jax.device_put(weights.seed_words(seed),
                                                device))
            server.seed = seed
            rounds = [server.serve_round(server.prompts(i),
                                         time.perf_counter())
                      for i in range(n_rounds)]
            picks = check.sample(n_rounds, B, k, seed)
            seqs, served = check.sequences(
                cell.traffic, dims["vocab"], seed, picks,
                lambda i, row: rounds[i].tokens[row])
            g = check.reference_gaps(ref, dims, server.params, seqs, served,
                                     cell.check["reference_block"],
                                     control=True)
            line = {
                "cell": cell.name, "seed": seed,
                "max_logit_gap": float(g["gap"].max()),
                "mean_logit_gap": float(g["gap"].mean()),
                "program_flips": int((g["gap"] > 0).sum()),
                "control_max_gap": float(g["control_gap"].max()),
                "control_mean_gap": float(g["control_gap"].mean()),
                "control_flips": int((g["control_gap"] > 0).sum()),
                "positions": int(g["gap"].size),
                "out_of_vocab": g["out_of_vocab"],
                "seconds": time.perf_counter() - t0,
            }
            print(json.dumps(line), flush=True)
            if out:
                out.write(json.dumps(line) + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
