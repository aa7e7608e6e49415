import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import (jax locks the device
# count at first init). Everything below is ordinary code.

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ARCH_NAMES, get_config, LONG_CONTEXT_OK  # noqa: E402
from repro.models.common import SHAPES                             # noqa: E402
from repro.launch.mesh import make_production_mesh                 # noqa: E402
from repro.launch import shardings as SH                           # noqa: E402
from repro.launch import specs as SP                               # noqa: E402
from repro.launch.roofline import (parse_collectives, roofline_terms,
                                   model_flops)                    # noqa: E402
from repro.launch.analytic import analytic_costs                   # noqa: E402
from repro.train import (make_train_step, make_prefill_step,
                         make_decode_step)                         # noqa: E402
from repro.parallel.act_sharding import activation_sharding        # noqa: E402
from repro.optim import AdamWConfig                                # noqa: E402

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell with
ShapeDtypeStruct stand-ins (no allocation), print memory/cost analysis, and
cache per-cell JSON for the roofline table (EXPERIMENTS.md §Dry-run).

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b \
        --shape train_4k [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""

# The chip the emulated production mesh stands for (a ``roofline.PEAKS``
# key): the host devices that compile here have no peaks of their own.
TARGET_DEVICE_KIND = "TPU v5 lite"

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _shape_by_name(name):
    for s in SHAPES:
        if s.name == name:
            return s
    raise KeyError(name)


def build_cell(arch: str, shape_name: str, multi_pod: bool,
               variant: str = ""):
    """Returns (jitted_fn, args, meta) for one cell. variant: optional
    hillclimb configuration tag (EXPERIMENTS §Perf), e.g. 'ring'."""
    cell = _shape_by_name(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dp = mesh.shape["data"] * (mesh.shape.get("pod", 1))
    cfg = get_config(arch)
    if cfg.n_experts and cell.global_batch * cell.seq_len % n_dp == 0:
        cfg = dataclasses.replace(cfg, moe_groups=n_dp)
    if variant == "ring":
        cfg = dataclasses.replace(cfg, ring_local_cache=True)
    elif variant == "ep":
        cfg = dataclasses.replace(cfg, moe_ep=True)

    bspec = SH.named(mesh, SH.batch_specs(cfg, cell, mesh))
    batch = SP.input_specs(cfg, cell)

    if cell.kind == "train":
        pstr = SP.param_structs(cfg)
        ostr = SP.opt_structs(pstr)
        pspec = SH.named(mesh, SH.param_specs(cfg, pstr, mesh, fsdp=True))
        ospec = {"mu": pspec, "nu": pspec,
                 "step": SH.named(mesh, jax.sharding.PartitionSpec())}
        fn = make_train_step(cfg, AdamWConfig(), use_flash=True,
                             grad_bf16=True)
        jfn = jax.jit(fn, in_shardings=(pspec, ospec, bspec),
                      out_shardings=(pspec, ospec, None))
        args = (pstr, ostr, batch)
        tokens = cell.global_batch * cell.seq_len
    elif cell.kind == "prefill":
        pstr = SP.param_structs(cfg, bf16=True)
        cstr = SP.cache_structs(cfg, cell)
        pspec = SH.named(mesh, SH.param_specs(cfg, pstr, mesh, fsdp=False))
        cspec = SH.named(mesh, SH.cache_specs(cfg, cell, mesh))
        fn = make_prefill_step(cfg)
        jfn = jax.jit(fn, in_shardings=(pspec, bspec, cspec),
                      out_shardings=(None, cspec))
        args = (pstr, batch, cstr)
        tokens = cell.global_batch * cell.seq_len
    else:                                       # decode
        pstr = SP.param_structs(cfg, bf16=True)
        cstr = SP.cache_structs(cfg, cell)
        pspec = SH.named(mesh, SH.param_specs(cfg, pstr, mesh, fsdp=False))
        cspec = SH.named(mesh, SH.cache_specs(cfg, cell, mesh))
        fn = make_decode_step(cfg)
        jfn = jax.jit(fn, in_shardings=(pspec, bspec["tokens"], None, cspec),
                      out_shardings=(None, cspec))
        args = (pstr, batch["tokens"], jax.ShapeDtypeStruct((), jnp.int32),
                cstr)
        tokens = cell.global_batch                 # one new token per seq
    meta = {"cfg": cfg, "cell": cell, "mesh": mesh, "tokens": tokens}
    return jfn, args, meta


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             force: bool = False, verbose: bool = True, variant: str = ""):
    mesh_tag = "pod512" if multi_pod else "pod256"
    if variant:
        mesh_tag = f"{mesh_tag}__{variant}"
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_tag}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
           "status": "ok"}
    if shape_name == "long_500k" and not LONG_CONTEXT_OK[arch]:
        rec["status"] = "skip"
        rec["reason"] = "pure full-attention arch; see DESIGN.md §4"
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        if verbose:
            print(f"[{arch} x {shape_name} x {mesh_tag}] SKIP "
                  f"({rec['reason']})")
        return rec

    try:
        t0 = time.time()
        jfn, args, meta = build_cell(arch, shape_name, multi_pod, variant)
        with activation_sharding(meta["mesh"]):
            lowered = jfn.lower(*args)      # constraints baked at trace time
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()

        try:
            mem = compiled.memory_analysis()
            rec["memory_analysis"] = str(mem)
            for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                         "output_size_in_bytes", "generated_code_size_in_bytes"):
                if hasattr(mem, attr):
                    rec[attr] = int(getattr(mem, attr))
        except Exception as e:                      # CPU backend may lack it
            rec["memory_analysis"] = f"unavailable on this backend: {e}"

        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
            rec["flops_per_device"] = float(ca.get("flops", 0.0))
            rec["bytes_per_device"] = float(ca.get("bytes accessed", 0.0))
        except Exception as e:
            rec["cost_analysis_error"] = str(e)
            rec["flops_per_device"] = 0.0
            rec["bytes_per_device"] = 0.0

        hlo = compiled.as_text()
        coll = parse_collectives(hlo)
        rec["collectives"] = coll
        rec["hlo_bytes"] = len(hlo)

        cfg, cell = meta["cfg"], meta["cell"]
        n_dev = meta["mesh"].size
        rec["n_devices"] = n_dev
        # Primary FLOPs/bytes are the analytic executed-work model (XLA-CPU
        # cost_analysis counts while bodies once — kept as a cross-check).
        ac = analytic_costs(cfg, cell)
        rec["analytic_flops"] = ac["flops"]
        rec["analytic_bytes"] = ac["bytes"]
        terms = roofline_terms(ac["flops"] / n_dev, ac["bytes"] / n_dev,
                               coll["total_bytes"],
                               device_kind=TARGET_DEVICE_KIND)
        rec["roofline"] = terms
        if cfg.encdec:
            enc_p, dec_p = cfg.encdec_split()
            B = cell.global_batch
            f = 6.0 if cell.kind == "train" else 2.0
            if cell.kind == "train":
                mf = f * (enc_p * B * cell.seq_len
                          + dec_p * B * cfg.max_dec_len)
            elif cell.kind == "prefill":
                mf = f * (enc_p * B * cell.seq_len + dec_p * B)
            else:
                mf = f * dec_p * B
        else:
            mf = model_flops(cfg.n_active_params(), meta["tokens"],
                             train=(cell.kind == "train"))
        rec["model_flops"] = mf
        rec["useful_flops_ratio"] = (mf / ac["flops"]) if ac["flops"] else 0.0
        rec["lower_s"] = t1 - t0
        rec["compile_s"] = t2 - t1
        if verbose:
            print(f"[{arch} x {shape_name} x {mesh_tag}] OK  "
                  f"flops={ac['flops']:.3e} bytes={ac['bytes']:.3e} "
                  f"coll/dev={coll['total_bytes']:.3e}  "
                  f"dominant={terms['dominant']} "
                  f"bound={terms['bound_s']*1e3:.2f}ms "
                  f"useful={rec['useful_flops_ratio']:.2f} "
                  f"temp/dev={rec.get('temp_size_in_bytes', 0)/1e9:.1f}GB "
                  f"(compile {rec['compile_s']:.0f}s)")
    except Exception as e:
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(f"[{arch} x {shape_name} x {mesh_tag}] FAIL: {rec['error']}")

    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=[s.name for s in SHAPES])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="",
                    help="hillclimb config tag (e.g. 'ring')")
    ap.add_argument("--out-dir", default=os.path.abspath(OUT_DIR))
    args = ap.parse_args()

    if args.all:
        import subprocess
        fails = []
        for arch in ARCH_NAMES:
            for s in SHAPES:
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", s.name,
                       "--out-dir", args.out_dir]
                if args.multi_pod:
                    cmd.append("--multi-pod")
                if args.force:
                    cmd.append("--force")
                r = subprocess.run(cmd, env=dict(os.environ))
                if r.returncode != 0:
                    fails.append((arch, s.name))
        if fails:
            print("FAILED CELLS:", fails)
            sys.exit(1)
        return

    if not (args.arch and args.shape):
        ap.error("--arch and --shape required (or --all)")
    rec = run_cell(args.arch, args.shape, args.multi_pod, args.out_dir,
                   force=args.force, variant=args.variant)
    if rec["status"] == "fail":
        sys.exit(1)


if __name__ == "__main__":
    main()
