"""Write a committed QoS policy file from a decode-TAF calibration sweep.

    python3 bench/tools/make_policy.py --config qwen3-1.7b --out PATH

Run once on the chip when a policy is (re)made; benchmark runs only load
the file. The sweep is the one `chip_smoke.py` runs before serving:
`qos.make_decode_app` (greedy decode of 2 x 8-token prompts, 12 new
tokens, weights from `--seed`), thresholds 0.02, 0.1 and 0.3 plus precise,
mismatch-rate metric, ladder ranked by modeled speedup. The file also
holds the class targets and the controller settings the benchmark's QoS
engine uses, and the sweep's records for the reader.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

THRESHOLDS = (0.02, 0.1, 0.3)
TARGETS = {"default": 0.10, "batch": 1.0}
MONITOR = {"sample_fraction": 0.25, "window": 8}
CONTROLLER = {"min_samples": 2, "hold_ticks": 2, "fallback_hold": 4}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="qwen3-1.7b")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from bench import cells
    from repro import qos
    from repro.core.harness import sweep

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"make_policy: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    cfg = cells.program_config(cells.load_config(ROOT, args.config),
                               approx=True)
    app = qos.make_decode_app(cfg, gen=12, seed=args.seed, metric="mcr")
    recs = sweep(app, qos.threshold_grid(cfg, THRESHOLDS), repeats=1)
    policy = qos.QosPolicy.from_records(recs, metric="mcr",
                                        use_modeled=True)
    doc = policy.to_json()
    doc.update(
        targets=TARGETS, monitor=MONITOR, controller=CONTROLLER,
        made_by=(f"bench/tools/make_policy.py --config {args.config} "
                 f"--seed {args.seed} on {dev.device_kind}"),
        sweep=[{"spec": dict(r.spec), "error": r.error,
                "approx_fraction": r.approx_fraction,
                "modeled_speedup": r.modeled_speedup,
                "wall_time_s": r.wall_time_s} for r in recs])
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps(doc["sweep"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
