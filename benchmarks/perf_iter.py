"""Perf-iteration driver: run one dry-run cell with config overrides and log
the result under benchmarks/artifacts/perf/<cell>__<tag>.json.

    PYTHONPATH=src python -m benchmarks.perf_iter --arch X --shape Y --tag T \
        [--overrides '{"attn_logits_dtype": "bfloat16"}'] [--grad-accum N] \
        [--multi-pod]
"""
import argparse
import json
import os

PERF_DIR = os.path.join(os.path.dirname(__file__), "artifacts", "perf")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--tag", required=True)
    ap.add_argument("--overrides", default=None)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--loss-chunk", type=int, default=1024)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--sp", action="store_true", help="sequence-parallel prefill")
    ap.add_argument("--dp", action="store_true", help="pure data parallelism")
    args = ap.parse_args()

    from repro.launch.dryrun import force_host_devices, run_cell
    force_host_devices()
    art = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   grad_accum=args.grad_accum, loss_chunk=args.loss_chunk,
                   overrides=json.loads(args.overrides) if args.overrides else None,
                   sp=args.sp, dp=args.dp)
    os.makedirs(PERF_DIR, exist_ok=True)
    path = os.path.join(PERF_DIR, f"{args.arch}__{args.shape}__{args.tag}.json")
    with open(path, "w") as f:
        json.dump(art, f, indent=1)
    r = art["roofline"]
    m = art["memory_analysis"]
    print(f"[{args.tag}] {args.arch} {args.shape} "
          f"compute={r['compute_s']:.3f}s mem={r['memory_s']:.3f}s "
          f"coll={r['collective_s']:.3f}s dom={r['dominant']} "
          f"useful={r['useful_ratio']:.2f} "
          f"frac={r['compute_s']/max(r['compute_s'],r['memory_s'],r['collective_s']):.2f} "
          f"GiB={(m['argument_bytes']+m['temp_bytes'])/2**30:.1f}")


if __name__ == "__main__":
    main()
