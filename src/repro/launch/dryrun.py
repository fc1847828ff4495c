"""Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh) cell.

MUST set the 512-placeholder-device flag before ANY other import (jax
locks device count on first init).
"""

import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse      # noqa: E402
import json          # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from functools import partial  # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ARCHS, get_config                     # noqa: E402
from repro.distributed import sharding as S                     # noqa: E402
from repro.launch.mesh import make_production_mesh              # noqa: E402
from repro.launch.specs import SHAPES, cell_runnable, input_specs  # noqa: E402
from repro.models import model as M                             # noqa: E402
from repro.models.config import QuantConfig                     # noqa: E402
from repro.optim.optimizer import (AdamWConfig, adamw_init,      # noqa: E402
                                   adamw_update, wsd_schedule)
from repro.serving import engine as E                           # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                "..", "..", ".."))
from benchmarks import hlo_analysis as H                        # noqa: E402

OUT_DIR = os.environ.get("DRYRUN_OUT", "/root/repo/experiments/dryrun")


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def _train_cell(cfg, mesh, specs, quant_override=None):
    """Lower train_step: fwd+bwd+AdamW(int8 state), donated state."""
    adamw = AdamWConfig(state_bits=8)
    sched = wsd_schedule(peak_lr=3e-4, warmup_steps=2000, total_steps=100000)

    params = jax.eval_shape(partial(M.init_params, cfg),
                            jax.random.PRNGKey(0))
    opt = jax.eval_shape(partial(adamw_init, cfg=adamw), params)

    def train_step(params, opt, batch):
        loss, grads = jax.value_and_grad(
            lambda p: M.loss_fn(p, batch, cfg))(params)
        lr = sched(opt.step)
        params, opt, stats = adamw_update(grads, opt, params, lr=lr,
                                          cfg=adamw)
        return params, opt, loss

    psh = S.shardings_for_params(mesh, params)
    osh = type(opt)(
        step=jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
        m=S.shardings_for_params(mesh, opt.m),
        v=S.shardings_for_params(mesh, opt.v),
        m_scale=S.shardings_for_params(mesh, opt.m_scale),
        v_scale=S.shardings_for_params(mesh, opt.v_scale))
    bsh = S.shardings_for_batch(mesh, specs)
    rsh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    fn = jax.jit(train_step,
                 in_shardings=(psh, osh, bsh),
                 out_shardings=(psh, osh, rsh),
                 donate_argnums=(0, 1))
    return fn.lower(params, opt, specs), params


def _serve_cell(cfg, mesh, specs, shape, mode):
    """Lower prefill_step / serve_step with quantized packed weights."""
    quant = cfg.quant
    params = jax.eval_shape(partial(M.init_params, cfg),
                            jax.random.PRNGKey(0))
    qparams = jax.eval_shape(partial(M.quantize_params, qcfg=quant), params)
    seq, batch = shape["seq"], shape["batch"]
    caches = jax.eval_shape(
        partial(M.init_caches, cfg, batch, seq, quant=quant))

    def step(params, batch_in, caches):
        if mode == "prefill":
            return E.prefill_step.__wrapped__(params, batch_in, caches, cfg,
                                              quant)
        return E.serve_step(params, batch_in, caches, cfg, quant)

    psh = S.shardings_for_params(mesh, qparams)
    bsh = S.shardings_for_batch(mesh, specs)
    csh = S.shardings_for_caches(mesh, caches)
    # logits (B, V): batch over DP where divisible (not for batch=1
    # long-context decode), vocab over model
    logits_sh = jax.sharding.NamedSharding(
        mesh, S._fit(mesh, (batch, cfg.vocab_padded),
                     (S._dp_axis(mesh), "model")))
    fn = jax.jit(step, in_shardings=(psh, bsh, csh),
                 out_shardings=(logits_sh, csh),
                 donate_argnums=(2,))
    return fn.lower(qparams, specs, caches), qparams


# ---------------------------------------------------------------------------
# per-cell runner
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = OUT_DIR, quiet: bool = False,
             opts: tuple = ()) -> dict:
    """opts (hillclimb levers, EXPERIMENTS.md §Perf):
       moe_tp      -- MoE experts TP-sharded on d_ff instead of EP
       attn_chunks -- pin the KV-chunk scan axis unsharded
       kv8         -- 8-bit bipolar KV cache
       bf16serve   -- disable weight quantization (paper FP baseline)
       bitserial   -- paper-faithful bit-serial APMM variant
    """
    import dataclasses as _dc
    from repro.models.config import QuantConfig as _QC
    cfg = get_config(arch)
    # the kv8 lever must stay a real A/B even though some shipped configs
    # default QuantConfig.kv_bits=8: cells pin the KV format explicitly
    kv = 8 if "kv8" in opts else None
    cfg = _dc.replace(cfg, kv_bits=kv,
                      quant=_dc.replace(cfg.quant, kv_bits=kv))
    if "bf16serve" in opts:
        cfg = _dc.replace(cfg, quant=_QC(w_bits=None))
    if "bitserial" in opts:
        cfg = _dc.replace(cfg, quant=_dc.replace(cfg.quant,
                                                 variant="bitserial"))
    if "attn_bf16" in opts:
        cfg = _dc.replace(cfg, attn_score_bf16=True)
    S.set_moe_mode("tp" if "moe_tp" in opts else "ep")
    shape = SHAPES[shape_name]
    mesh_tag = "pod512" if multi_pod else "pod256"
    cell_id = f"{arch}__{shape_name}__{mesh_tag}"
    if opts:
        cell_id += "__opt-" + "-".join(sorted(opts))
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, cell_id + ".json")

    ok, reason = cell_runnable(cfg, shape_name)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_tag,
        "opts": list(opts),
        "mode": shape["mode"], "seq": shape["seq"], "batch": shape["batch"],
        "n_chips": 512 if multi_pod else 256,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "w_bits": cfg.quant.w_bits, "a_bits": cfg.quant.a_bits,
    }
    if not ok:
        rec.update(status="skipped", reason=reason)
        json.dump(rec, open(out_path, "w"), indent=1)
        return rec

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        S.set_activation_context(
            mesh, extra=("attn_chunks",) if "attn_chunks" in opts else ())
        specs = input_specs(cfg, shape_name)
        if shape["mode"] == "train":
            lowered, _ = _train_cell(cfg, mesh, specs)
        else:
            lowered, _ = _serve_cell(cfg, mesh, specs, shape, shape["mode"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        ma = compiled.memory_analysis()
        ca = H.xla_cost_analysis(compiled)
        hlo = H.analyze(compiled.as_text())
        rec.update(
            status="ok",
            lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
            memory=dict(
                argument_bytes=int(ma.argument_size_in_bytes),
                output_bytes=int(ma.output_size_in_bytes),
                temp_bytes=int(ma.temp_size_in_bytes),
                peak_bytes=int(ma.peak_memory_in_bytes),
                alias_bytes=int(ma.alias_size_in_bytes),
            ),
            cost_analysis=dict(
                flops=float(ca.get("flops", 0)),
                bytes_accessed=float(ca.get("bytes accessed", 0)),
            ),
            hlo=dict(
                dot_flops=float(hlo.get("dot_flops", 0)),
                dot_flops_int=float(hlo.get("dot_flops_int", 0)),
                dot_flops_f32=float(hlo.get("dot_flops_f32", 0)),
                dot_flops_bf16=float(hlo.get("dot_flops_bf16", 0)),
                bytes=float(hlo.get("bytes", 0)),
                collective_bytes=float(hlo.get("collective_bytes", 0)),
                n_collective_ops=int(hlo.get("n_collective_ops", 0)),
                collectives={k: float(v)
                             for k, v in hlo.get("collectives", {}).items()},
                top_ops=[dict(name=o["name"][-120:], opcode=o["opcode"],
                              bytes=float(o["bytes"]),
                              flops=float(o["flops"]))
                         for o in hlo.get("top_ops", [])],
            ),
        )
    except Exception as e:  # noqa: BLE001 -- a cell failure is a bug report
        rec.update(status="failed", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    json.dump(rec, open(out_path, "w"), indent=1)
    if not quiet:
        peak = rec.get("memory", {}).get("peak_bytes", 0) / 2**30
        print(f"[{cell_id}] {rec['status']} "
              f"peak={peak:.2f}GiB "
              f"compile={rec.get('compile_s', 0)}s "
              f"{rec.get('error', '')}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all",
                    help="shape name or 'all'")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--force", action="store_true",
                    help="re-run cells with existing results")
    ap.add_argument("--opt", default="",
                    help="comma-separated hillclimb levers "
                         "(moe_tp,attn_chunks,kv8,bf16serve,bitserial)")
    args = ap.parse_args()
    opts = tuple(o for o in args.opt.split(",") if o)

    archs = list(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = 0
    for multi in meshes:
        for arch in archs:
            for shape in shapes:
                tag = "pod512" if multi else "pod256"
                name = f"{arch}__{shape}__{tag}"
                if opts:
                    name += "__opt-" + "-".join(sorted(opts))
                p = os.path.join(args.out, name + ".json")
                if os.path.exists(p) and not args.force:
                    rec = json.load(open(p))
                    if rec.get("status") in ("ok", "skipped"):
                        continue
                rec = run_cell(arch, shape, multi, args.out, opts=opts)
                failures += rec["status"] == "failed"
    print(f"dry-run sweep done, failures={failures}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
