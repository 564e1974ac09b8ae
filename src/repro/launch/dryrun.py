"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes and extract the roofline terms.

MUST be run as a module (python -m repro.launch.dryrun ...) so the
device-count override below precedes any jax initialization.  The run
pins itself to the CPU: on a machine with a chip, a dry-run child that
initialized the TPU would take it from the process that owns it.
Importing the module (for its helpers) changes neither the platform
nor XLA_FLAGS of the importing process.
"""
import os

_HOST_DEVICES_FLAG = "--xla_force_host_platform_device_count=512"


def _cpu_env(env):
    """`env` pinned to the CPU with the production device count."""
    env = dict(env)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = " ".join(filter(None, (env.get("XLA_FLAGS"),
                                              _HOST_DEVICES_FLAG)))
    return env


if __name__ == "__main__":
    os.environ.update(_cpu_env(os.environ))
    import jax
    # the package import already read JAX_PLATFORMS; pin the config too
    jax.config.update("jax_platforms", "cpu")

import argparse     # noqa: E402
import json         # noqa: E402
import subprocess   # noqa: E402
import sys          # noqa: E402
import time         # noqa: E402
import traceback    # noqa: E402

import jax          # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import META, SHAPES, cells, get_config  # noqa: E402
from repro.distributed import sharding as shard_lib  # noqa: E402
from repro.launch import hlo_analysis  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import model as model_lib  # noqa: E402
from repro.optim.adamw import AdamWConfig, init_state  # noqa: E402

# TPU v5e hardware constants (per chip)
PEAK_FLOPS = 197e12         # bf16
HBM_BW = 819e9              # bytes/s
ICI_BW = 50e9               # bytes/s per link (one effective ring link)


def input_specs(cfg, shape_name: str, grad_accum: int = 1):
    """ShapeDtypeStruct stand-ins for every model input of the cell."""
    sh = SHAPES[shape_name]
    b, s = sh["batch"], sh["seq"]
    tok = jnp.int32
    emb = jnp.dtype(cfg.dtype)
    if sh["kind"] == "train":
        if cfg.input_mode == "tokens":
            inp = jax.ShapeDtypeStruct((b, s), tok)
        else:
            inp = jax.ShapeDtypeStruct((b, s, cfg.d_model), emb)
        lab = jax.ShapeDtypeStruct((b, s), tok)
        batch = {"inputs": inp, "labels": lab}
        if grad_accum > 1:
            batch = {k: jax.ShapeDtypeStruct(
                (grad_accum, v.shape[0] // grad_accum) + v.shape[1:],
                v.dtype) for k, v in batch.items()}
        return batch
    if sh["kind"] == "prefill":
        if cfg.input_mode == "tokens":
            return {"inputs": jax.ShapeDtypeStruct((b, s), tok)}
        return {"inputs": jax.ShapeDtypeStruct((b, s, cfg.d_model), emb)}
    # decode: one new token against a seq_len cache
    if cfg.input_mode == "tokens":
        inp = jax.ShapeDtypeStruct((b, 1), tok)
    else:
        inp = jax.ShapeDtypeStruct((b, 1, cfg.d_model), emb)
    cache = jax.eval_shape(lambda: model_lib.init_cache(cfg, b, s))
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return {"inputs": inp, "cache": cache, "pos": pos}


def model_flops(cfg, shape_name: str) -> float:
    """Useful FLOPs: 6*N_active*D train / 2*N_active*D inference, plus
    attention O(S^2 d) for the causal/local pattern actually configured."""
    sh = SHAPES[shape_name]
    b, s = sh["batch"], sh["seq"]
    n_act = cfg.active_param_count()
    attn = 0.0
    if cfg.ssm_kind is None:
        hd, h = cfg.hd, cfg.n_heads
        for i in range(cfg.n_layers):
            kind = cfg.attn_kind(i)
            if sh["kind"] == "decode":
                kv = min(s, cfg.local_window) if kind == "local" else s
                attn += 2 * 2 * b * h * hd * kv          # qk + pv
            else:
                kv = min(s, cfg.local_window) if kind == "local" else s
                attn += 2 * 2 * b * h * hd * s * kv / (
                    1 if kind == "local" else 2)          # causal half
    if sh["kind"] == "train":
        return 6 * n_act * b * s + 3 * attn
    if sh["kind"] == "prefill":
        return 2 * n_act * b * s + attn
    return 2 * n_act * b + attn                            # decode: 1 tok


def _parse_overrides(s):
    """--opt 'attn_schedule=triangular,megatron_sp=true,grad_accum=4'."""
    out = {}
    if not s:
        return out
    for kv in s.split(","):
        k, v = kv.split("=", 1)
        if v.lower() in ("true", "false"):
            out[k] = v.lower() == "true"
        else:
            try:
                out[k] = int(v)
            except ValueError:
                try:
                    out[k] = float(v)
                except ValueError:
                    out[k] = v
    return out


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               overrides=None):
    meta = dict(META[arch])
    cfg = get_config(arch)
    ov = dict(overrides or {})
    for k in ("grad_accum", "fsdp", "seq_shard", "moments"):
        if k in ov:
            meta[k] = ov.pop(k)
    ep_data = bool(ov.pop("ep_data", False))
    if ov:
        cfg = cfg.replace(**ov)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    sh = SHAPES[shape_name]
    kind = sh["kind"]
    accum = meta["grad_accum"] if kind == "train" else 1
    # each microbatch must still cover the DP axes
    dp_size = int(np.prod([mesh.shape[a] for a in ("pod", "data")
                           if a in mesh.shape]))
    accum = max(1, min(accum, sh["batch"] // dp_size))

    abs_params = model_lib.abstract_init(cfg)
    fsdp_axes = ("pod", "data") if multi_pod else ("data",)
    pspecs = shard_lib.param_spec_tree(abs_params, cfg, fsdp=meta["fsdp"],
                                       fsdp_axes=fsdp_axes,
                                       ep_data=ep_data)
    pshard = shard_lib.named_sharding_tree(pspecs, mesh)
    acts = shard_lib.act_specs(mesh, seq_shard=meta["seq_shard"],
                               ep_data=ep_data)
    specs = input_specs(cfg, shape_name, grad_accum=accum)
    dp = shard_lib.dp_axes(mesh)

    with mesh, shard_lib.activation_specs(acts):
        if kind == "train":
            from repro.launch.train import TrainConfig, make_train_step
            tcfg = TrainConfig(grad_accum=accum, optimizer=AdamWConfig(
                moment_dtype=meta.get("moments", "float32")))
            step = make_train_step(cfg, tcfg)
            abs_opt = jax.eval_shape(
                lambda: init_state(abs_params, tcfg.optimizer))
            oshard = {"m": pshard, "v": pshard,
                      "count": NamedSharding(mesh, P())}
            lead = (None,) if accum > 1 else ()
            bshard = {
                "inputs": NamedSharding(mesh, P(*lead, dp, *([None] * (
                    1 if cfg.input_mode == "tokens" else 2)))),
                "labels": NamedSharding(mesh, P(*lead, dp, None)),
            }
            fn = jax.jit(step, in_shardings=(pshard, oshard, bshard),
                         donate_argnums=(0, 1))
            lowered = fn.lower(abs_params, abs_opt, specs)
        elif kind == "prefill":
            from repro.models.model import prefill
            bshard = NamedSharding(mesh, P(dp, *([None] * (
                1 if cfg.input_mode == "tokens" else 2))))
            fn = jax.jit(lambda p, x: prefill(p, x, cfg),
                         in_shardings=(pshard, bshard))
            lowered = fn.lower(abs_params, specs["inputs"])
        else:  # decode
            from repro.models.model import decode_step
            b = sh["batch"]
            cshard = shard_lib.cache_spec_tree(specs["cache"], cfg, mesh, b)
            dp_size = int(np.prod([mesh.shape[a] for a in dp]))
            bax = dp if (b >= dp_size and b % dp_size == 0) else None
            ishard = NamedSharding(mesh, P(bax, *([None] * (
                1 if cfg.input_mode == "tokens" else 2))))
            fn = jax.jit(
                lambda p, x, c, pos: decode_step(p, x, c, pos, cfg),
                in_shardings=(pshard, ishard, cshard,
                              NamedSharding(mesh, P())),
                donate_argnums=(2,))
            lowered = fn.lower(abs_params, specs["inputs"],
                               specs["cache"], specs["pos"])

        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    cost = hlo_analysis.analyze(txt)
    hlo_out = os.environ.get("DRYRUN_HLO_OUT")
    if hlo_out:
        import gzip
        with gzip.open(hlo_out, "wt") as f:
            f.write(txt)

    useful = model_flops(cfg, shape_name)
    per_dev_useful = useful / chips
    compute_s = cost.flops / PEAK_FLOPS
    memory_s = cost.bytes_accessed / HBM_BW
    coll_s = cost.coll_wire_bytes / ICI_BW
    dominant = max((("compute", compute_s), ("memory", memory_s),
                    ("collective", coll_s)), key=lambda kv: kv[1])[0]
    return {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips, "kind": kind, "grad_accum": accum,
        "compile_s": round(compile_s, 1),
        "mem": {
            "argument_gib": ma.argument_size_in_bytes / 2**30,
            "output_gib": ma.output_size_in_bytes / 2**30,
            "temp_gib": ma.temp_size_in_bytes / 2**30,
            "alias_gib": ma.alias_size_in_bytes / 2**30,
            "peak_est_gib": (ma.argument_size_in_bytes
                             + ma.output_size_in_bytes
                             + ma.temp_size_in_bytes
                             - ma.alias_size_in_bytes) / 2**30,
        },
        "hlo": {
            "flops_per_dev": cost.flops,
            "bytes_per_dev": cost.bytes_accessed,
            "coll_bytes_per_dev": cost.coll_bytes,
            "coll_wire_bytes_per_dev": cost.coll_wire_bytes,
            "coll_by_type": dict(cost.coll_by_type),
            "coll_count": dict(cost.coll_count),
            "xla_cost_flops_unrolled_once": ca.get("flops", -1),
        },
        "roofline": {
            "compute_s": compute_s, "memory_s": memory_s,
            "collective_s": coll_s, "dominant": dominant,
            "model_flops_total": useful,
            "model_flops_per_dev": per_dev_useful,
            "useful_ratio": per_dev_useful / max(cost.flops, 1.0),
            "roofline_s": max(compute_s, memory_s, coll_s),
            "roofline_frac": min(1.0, per_dev_useful / PEAK_FLOPS
                                 / max(compute_s, memory_s, coll_s)),
        },
    }


def run_cell_subprocess(arch, shape, mesh_kind, out_path, opt=None):
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--mesh", mesh_kind, "--json-out", out_path]
    if opt:
        cmd += ["--opt", opt]
    env = _cpu_env(os.environ)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
    env["DRYRUN_HLO_OUT"] = out_path.replace(".json", ".hlo.gz")
    return subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=7200)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--json-out", default=None)
    ap.add_argument("--results-dir", default="results/dryrun")
    ap.add_argument("--jobs", type=int, default=3)
    ap.add_argument("--opt", default=None,
                    help="cfg/meta overrides, e.g. "
                         "attn_schedule=triangular,megatron_sp=true")
    args = ap.parse_args()

    if args.all:
        os.makedirs(args.results_dir, exist_ok=True)
        meshes = (["single", "multi"] if args.mesh == "both"
                  else [args.mesh])
        jobs = []
        for arch, shape, skipped in cells():
            for mk in meshes:
                out = os.path.join(args.results_dir,
                                   f"{arch}__{shape}__{mk}.json")
                if os.path.exists(out):
                    print(f"skip (cached): {out}")
                    continue
                jobs.append((arch, shape, mk, out))
        import concurrent.futures as cf
        with cf.ThreadPoolExecutor(args.jobs) as ex:
            futs = {ex.submit(run_cell_subprocess, *j): j for j in jobs}
            for f in cf.as_completed(futs):
                arch, shape, mk, out = futs[f]
                r = f.result()
                ok = r.returncode == 0 and os.path.exists(out)
                print(f"[{'OK' if ok else 'FAIL'}] {arch} {shape} {mk}")
                if not ok:
                    print(r.stdout[-2000:])
                    print(r.stderr[-4000:])
        return

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    records = []
    for mp in meshes:
        rec = lower_cell(args.arch, args.shape, multi_pod=mp,
                         overrides=_parse_overrides(args.opt))
        if args.opt:
            rec["overrides"] = args.opt
        records.append(rec)
        r = rec["roofline"]
        print(f"== {args.arch} {args.shape} mesh={rec['mesh']} "
              f"compile={rec['compile_s']}s")
        print(f"   mem/device: {rec['mem']['peak_est_gib']:.2f} GiB "
              f"(args {rec['mem']['argument_gib']:.2f} + temps "
              f"{rec['mem']['temp_gib']:.2f})")
        print(f"   roofline: compute={r['compute_s']:.4f}s "
              f"memory={r['memory_s']:.4f}s coll={r['collective_s']:.4f}s "
              f"-> {r['dominant']}-bound, useful_ratio="
              f"{r['useful_ratio']:.3f} frac={r['roofline_frac']:.3f}")
        print(f"   collectives: {rec['hlo']['coll_count']}")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(records if len(records) > 1 else records[0], f,
                      indent=2)


if __name__ == "__main__":
    try:
        main()
    except Exception:
        traceback.print_exc()
        sys.exit(1)
