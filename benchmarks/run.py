# One function per paper table. Print ``name,us_per_call,derived`` CSV
# and mirror the rows to a machine-readable artifact: by default
# ``BENCH_<tag>.json`` at the repo root (tag = jax backend), so every
# benchmark run leaves a comparable point on the perf trajectory.
# ``--json PATH`` overrides the path, ``--no-json`` suppresses it.
import argparse
import os

#: every suite ``--only`` accepts.  ``backend`` is opt-in only (the
#: per-target lambda-vs-bounding A/B rows are also part of map/attn),
#: hence its absence from the default no-``--only`` sweep below.
SUITES = ("map", "space", "time", "ca", "sched", "shard", "overlap",
          "attn", "backend", "serve")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: " + ",".join(SUITES) + " (backend "
                         "= the per-target lambda-vs-bounding A/B rows "
                         "alone; they are also part of map/attn)")
    ap.add_argument("--json", default=None,
                    help="artifact path (default: BENCH_<tag>.json at "
                         "the repo root)")
    ap.add_argument("--no-json", action="store_true",
                    help="skip the JSON artifact")
    ap.add_argument("--tag", default=None,
                    help="artifact tag (default: jax backend)")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None
    if only is not None:
        unknown = sorted(only - set(SUITES))
        if unknown:
            ap.error(f"unknown suite(s) {', '.join(unknown)}; "
                     f"available: {', '.join(SUITES)}")

    import jax

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    from . import (bench_attention_domains, bench_ca, bench_map_time,
                   bench_serve, bench_sierpinski_map,
                   bench_space_efficiency, common)

    print("name,us_per_call,derived")
    if only is None or "map" in only:
        bench_sierpinski_map.run()
    if only is None or "space" in only:
        bench_space_efficiency.run()
    if only is None or "time" in only:
        bench_map_time.run()
    if only is None or "sched" in only:
        bench_ca.run_sched_ab()
    if only is None or "shard" in only:
        bench_ca.run_shard_ab()
    if only is None or "overlap" in only:
        bench_ca.run_overlap_ab()
    if only is None or "ca" in only:
        bench_ca.run(sched_ab=False)
    if only is None or "attn" in only:
        bench_attention_domains.run()
    if only is None or "serve" in only:
        bench_serve.run()
        bench_serve.run_page_sizes()
        bench_serve.run_zigzag_balance()
    if only is not None and "backend" in only:
        bench_sierpinski_map.run_backend_ab()
        bench_attention_domains.run_backend_ab()
    if not args.no_json:
        path = args.json
        if path is None:
            tag = args.tag or jax.default_backend()
            root = os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))
            path = os.path.join(root, f"BENCH_{tag}.json")
        common.dump_json(path)


if __name__ == '__main__':
    main()
