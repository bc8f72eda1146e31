import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count="
                           + os.environ.get("REPRO_DEVICES", "512"))
"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell on placeholder devices, then derive the roofline terms.

MUST be run as its own process (the device-count flag above is set before
any jax import, and only here — tests/benches see the real single device):

    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b \
        --shape train_4k --mesh multi
    PYTHONPATH=src python -m repro.launch.dryrun --gp   # paper-technique cells

Outputs one JSON per cell under experiments/dryrun/ (memory analysis, cost
analysis, collective bytes, roofline terms).
"""
import argparse
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.configs.registry import ARCH_NAMES, get_config
from repro.configs.shapes import SHAPES, applicable
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.launch import serve as serve_lib
from repro.launch import train as train_lib
from repro.models import transformer as tf
from repro.optim.adam import Adam
from repro.parallel import sharding as shd
from repro.roofline import analysis, hlo_parse

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


# ---------------------------------------------------------------------------
# abstract inputs / states
# ---------------------------------------------------------------------------

def batch_sds(cfg: ModelConfig, shape):
    B, T = shape.global_batch, shape.seq_len
    b = {"tokens": jax.ShapeDtypeStruct((B, T), jnp.int32),
         "labels": jax.ShapeDtypeStruct((B, T), jnp.int32)}
    if cfg.family == "vlm":
        b["inputs_embeds"] = jax.ShapeDtypeStruct((B, T, cfg.d_model),
                                                  jnp.bfloat16)
    if cfg.enc_dec:
        b["frames"] = jax.ShapeDtypeStruct((B, cfg.enc_seq, cfg.d_model),
                                           jnp.bfloat16)
    return b


def sharded_param_bytes(tree_sds, specs, mesh) -> float:
    """Per-device bytes of a sharded pytree (analytic)."""
    total = 0.0
    for sds, spec in zip(jax.tree.leaves(tree_sds),
                         jax.tree.leaves(
                             specs, is_leaf=lambda x: isinstance(x, P))):
        n = 1
        for axes in spec:
            if axes is None:
                continue
            for a in (axes,) if isinstance(axes, str) else axes:
                n *= mesh.shape[a]
        total += sds.size * sds.dtype.itemsize / n
    return total


# ---------------------------------------------------------------------------
# FLOP probe: three-point layer solve on unoptimized HLO (scan trip 1 is
# counted exactly; see roofline/analysis.py docstring)
# ---------------------------------------------------------------------------

def _probe_lower(cfg, shape, kind, moe_groups=1, ring_cache=False,
                 last_logits=False):
    B, T = shape.global_batch, shape.seq_len

    if kind == "decode":
        def step(params, token, state):
            logits, st = tf.decode_step(params, token, state, cfg,
                                        moe_groups=min(moe_groups, B) or 1)
            return logits

        def mk():
            p = tf.init_model(jax.random.PRNGKey(0), cfg)
            st = tf.init_serve(cfg, B, T + 8, enc_kv=None,
                               ring_cache=ring_cache)
            if cfg.enc_dec:
                enc_arr = jnp.zeros((B, cfg.enc_seq, cfg.d_model),
                                    jnp.bfloat16)
                st = st._replace(cross_kv=tf.precompute_cross_kv(
                    p, enc_arr, cfg))
            return p, st

        params, state = jax.eval_shape(mk)
        tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        return jax.jit(step).lower(params, tok, state)

    batch = batch_sds(cfg, shape)

    def loss_fn(params, batch):
        enc_kv = None
        if cfg.enc_dec:
            enc_kv = tf.encode(params, batch["frames"], cfg, attn_impl="jnp")
        return tf.lm_loss(params, batch.get("tokens"), batch["labels"], cfg,
                          enc_kv=enc_kv,
                          inputs_embeds=batch.get("inputs_embeds"),
                          attn_impl="jnp", moe_groups=moe_groups)[0]

    if kind == "train":
        fn = lambda p, b: jax.grad(loss_fn)(p, b)
    elif last_logits:  # serving prefill: last-position logits only
        def fn(p, b):
            enc_kv = None
            if cfg.enc_dec:
                enc_kv = tf.encode(p, b["frames"], cfg, attn_impl="jnp")
            return tf.forward(p, b.get("tokens"), cfg, enc_kv=enc_kv,
                              inputs_embeds=b.get("inputs_embeds"),
                              attn_impl="jnp", moe_groups=moe_groups,
                              logits_last_only=True)[0]
    else:  # prefill as loss-forward
        fn = lambda p, b: loss_fn(p, b)
    params = jax.eval_shape(lambda: tf.init_model(jax.random.PRNGKey(0), cfg))
    return jax.jit(fn).lower(params, batch)


def probe_flops(cfg: ModelConfig, shape, kind, moe_groups=1,
                ring_cache=False, last_logits=False) -> float:
    period = cfg.period
    plan = cfg.plan()
    n_full = len(plan) // period
    n_rest = len(plan) % period

    def flops_of(n_layers, enc_layers):
        c = cfg.scaled(n_layers=n_layers,
                       enc_layers=enc_layers if cfg.enc_dec else 0)
        lw = _probe_lower(c, shape, kind, moe_groups, ring_cache,
                          last_logits)
        return float((lw.cost_analysis() or {}).get("flops", 0.0))

    e1 = 1 if cfg.enc_dec else 0
    f0 = flops_of(0, e1)
    f1 = flops_of(period, e1)
    total = f0 + n_full * (f1 - f0)
    if n_rest:
        f2 = flops_of(period + n_rest, e1)
        total += f2 - f1
    if cfg.enc_dec and kind != "decode":
        f0e2 = flops_of(0, 2)
        total += (cfg.enc_layers - 1) * (f0e2 - f0)
    return total


# ---------------------------------------------------------------------------
# cell lowering on the production mesh
# ---------------------------------------------------------------------------

def lower_cell(cfg: ModelConfig, shape, mesh, ring_cache: bool = False,
               serve_bf16: bool = False, last_logits: bool = False):
    dp = shd.dp_axes(mesh)
    moe_groups = 1
    for a in dp:
        moe_groups *= mesh.shape[a]
    kind = shape.kind

    if kind == "decode":
        B, T = shape.global_batch, shape.seq_len

        def step(params, token, state):
            return tf.decode_step(params, token, state, cfg,
                                  moe_groups=min(moe_groups, B) or 1)

        # +512 headroom keeps max_len divisible by every DP factor so the
        # sequence-sharded (batch=1) cache layout is valid
        def mk():
            dt = jnp.bfloat16 if serve_bf16 else jnp.float32
            p = tf.init_model(jax.random.PRNGKey(0), cfg, dtype=dt)
            st = tf.init_serve(cfg, B, T + 512, enc_kv=None,
                               ring_cache=ring_cache)
            if cfg.enc_dec:
                enc_arr = jnp.zeros((B, cfg.enc_seq, cfg.d_model),
                                    jnp.bfloat16)
                st = st._replace(cross_kv=tf.precompute_cross_kv(
                    p, enc_arr, cfg))
            return p, st

        params, state = jax.eval_shape(mk)
        tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        pspec = shd.param_specs(params, mesh)
        sspec = serve_lib.serve_state_specs(cfg, mesh, batch=B)
        lowered = jax.jit(
            step,
            in_shardings=(shd.shardings(pspec, mesh),
                          NamedSharding(mesh, shd.batch_spec(mesh)
                                        if B > 1 else P()),
                          shd.shardings(sspec, mesh)),
            out_shardings=(NamedSharding(
                mesh, shd.logits_spec(mesh, batch=B, vocab=cfg.vocab_padded)),
                           shd.shardings(sspec, mesh)),
        ).lower(params, tok, state)
        state_bytes = sharded_param_bytes(state, sspec, mesh)
        param_bytes = sharded_param_bytes(params, pspec, mesh)
        return lowered, param_bytes + state_bytes

    batch = batch_sds(cfg, shape)
    params = jax.eval_shape(lambda: tf.init_model(jax.random.PRNGKey(0), cfg))
    use_tp = shd.use_tp_policy(params)
    B = shape.global_batch
    if not use_tp and B % (moe_groups * mesh.shape.get("model", 1)) == 0:
        moe_groups = moe_groups * mesh.shape.get("model", 1)
    pspec = shd.param_specs(params, mesh, use_tp=use_tp)
    bspec = {k: shd.batch_spec(mesh, use_tp=use_tp, batch=B)
             for k in batch}

    def loss_fn(params, batch):
        enc_kv = None
        if cfg.enc_dec:
            enc_kv = tf.encode(params, batch["frames"], cfg, attn_impl="jnp")
        return tf.lm_loss(params, batch.get("tokens"), batch["labels"], cfg,
                          enc_kv=enc_kv,
                          inputs_embeds=batch.get("inputs_embeds"),
                          attn_impl="jnp", remat=True,
                          moe_groups=moe_groups)[0]

    if kind == "train":
        opt = Adam(lr=1e-4)

        def step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            new_params, new_opt = opt.update(grads, opt_state, params)
            return loss, new_params, new_opt

        opt_state = jax.eval_shape(lambda: opt.init(params))
        ospec = train_lib.AdamState(P(), pspec, pspec)
        lowered = jax.jit(
            step,
            in_shardings=(shd.shardings(pspec, mesh),
                          shd.shardings(ospec, mesh),
                          shd.shardings(bspec, mesh)),
            out_shardings=(NamedSharding(mesh, P()),
                           shd.shardings(pspec, mesh),
                           shd.shardings(ospec, mesh)),
        ).lower(params, opt_state, batch)
        mem = (sharded_param_bytes(params, pspec, mesh) * 3)  # p + m + v
    else:  # prefill
        if last_logits:
            def prefill_fn(params, batch):
                enc_kv = None
                if cfg.enc_dec:
                    enc_kv = tf.encode(params, batch["frames"], cfg,
                                       attn_impl="jnp")
                return tf.forward(params, batch.get("tokens"), cfg,
                                  enc_kv=enc_kv,
                                  inputs_embeds=batch.get("inputs_embeds"),
                                  attn_impl="jnp", moe_groups=moe_groups,
                                  logits_last_only=True)[0]
            out_sh = NamedSharding(mesh, shd.logits_spec(
                mesh, batch=shape.global_batch, vocab=cfg.vocab_padded))
            lowered = jax.jit(
                prefill_fn,
                in_shardings=(shd.shardings(pspec, mesh),
                              shd.shardings(bspec, mesh)),
                out_shardings=out_sh,
            ).lower(params, batch)
        else:
            lowered = jax.jit(
                loss_fn,
                in_shardings=(shd.shardings(pspec, mesh),
                              shd.shardings(bspec, mesh)),
                out_shardings=NamedSharding(mesh, P()),
            ).lower(params, batch)
        mem = sharded_param_bytes(params, pspec, mesh)
    return lowered, mem


def _make_mesh(mesh_name: str):
    """Production meshes, or tiny test meshes when REPRO_DEVICES is small
    (debugging the cell plumbing without the 512-device compile cost)."""
    n_dev = len(jax.devices())
    multi = mesh_name == "multi"
    if n_dev >= 512:
        return make_production_mesh(multi_pod=multi), (512 if multi else 256)
    if multi:
        shape = (2, 2, n_dev // 4)
        return make_mesh(shape, ("pod", "data", "model")), n_dev
    return make_mesh((2, n_dev // 4), ("data", "model")), n_dev // 2


def run_cell(arch: str, shape_name: str, mesh_name: str, *,
             skip_probe=False, overrides=None,
             ring_cache: bool = False, serve_bf16: bool = False,
             last_logits: bool = False) -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.scaled(**overrides)
    shape = SHAPES[shape_name]
    mesh, chips = _make_mesh(mesh_name)
    t0 = time.time()
    lowered, static_bytes = lower_cell(cfg, shape, mesh,
                                       ring_cache=ring_cache,
                                       serve_bf16=serve_bf16,
                                       last_logits=last_logits)
    if serve_bf16:
        static_bytes = static_bytes  # cache dtypes already bf16; params halve

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    mem_info = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes"):
        mem_info[attr] = getattr(mem, attr, None)
    print(compiled.memory_analysis())
    print({k: v for k, v in (compiled.cost_analysis() or {}).items()
           if k in ("flops", "bytes accessed")})

    dp_prod = 1
    for a in shd.dp_axes(mesh):
        dp_prod *= mesh.shape[a]
    pf = (probe_flops(cfg, shape, shape.kind, moe_groups=dp_prod,
                      ring_cache=ring_cache, last_logits=last_logits)
          if not skip_probe else 0.0)

    class _Probe:  # adapter for analysis.analyze
        def cost_analysis(self):
            return {"flops": pf}

    roof = analysis.analyze(
        arch, shape_name, mesh_name, chips=chips, compiled=compiled,
        probe_lowered=_Probe(), cfg=cfg, shape=shape,
        bytes_per_device=static_bytes, ring_cache=ring_cache,
        param_bytes_each=2.0 if serve_bf16 else 4.0,
        last_logits=last_logits)
    rec = roof.to_json()
    rec.update({"memory_analysis": mem_info, "lower_s": t_lower,
                "compile_s": t_compile,
                "cost_analysis": {k: v for k, v in
                                  (compiled.cost_analysis() or {}).items()
                                  if k in ("flops", "bytes accessed",
                                           "transcendentals")}})
    return rec


# ---------------------------------------------------------------------------
# GP (paper technique) dry-run cells
# ---------------------------------------------------------------------------

def run_gp_cell(method: str, mesh_name: str, *, n=1 << 20, s=2048, u=1 << 15,
                r=2048, d=8) -> dict:
    from repro.core import covariance as cov, ppic, ppitc, picf
    from repro.parallel.runner import ShardMapRunner

    mesh, chips = _make_mesh(mesh_name)
    axes = tuple(mesh.axis_names)
    runner = ShardMapRunner(mesh=mesh, axis_name=axes)
    M = runner.num_machines
    kfn = cov.make_kernel("se")
    params = jax.eval_shape(lambda: cov.init_params(d))
    X = jax.ShapeDtypeStruct((n, d), jnp.float32)
    y = jax.ShapeDtypeStruct((n,), jnp.float32)
    S = jax.ShapeDtypeStruct((s, d), jnp.float32)
    U = jax.ShapeDtypeStruct((u, d), jnp.float32)

    if method == "ppitc":
        fn = lambda p, S, X, y, U: ppitc.predict(kfn, p, S, X, y, U, runner)
        args = (params, S, X, y, U)
    elif method == "ppic":
        fn = lambda p, S, X, y, U: ppic.predict(kfn, p, S, X, y, U, runner)
        args = (params, S, X, y, U)
    else:
        fn = lambda p, X, y, U: picf.predict(kfn, p, X, y, U, r, runner,
                                             shard_u=True)
        args = (params, X, y, U)

    t0 = time.time()
    lowered = jax.jit(fn).lower(*args)
    compiled = lowered.compile()
    t_compile = time.time() - t0
    print(compiled.memory_analysis())
    coll = hlo_parse.collective_bytes(compiled.as_text())
    ca = compiled.cost_analysis() or {}
    return {"method": method, "mesh": mesh_name, "chips": chips, "M": M,
            "n": n, "s": s, "u": u, "r": r,
            "flops": ca.get("flops"), "bytes": ca.get("bytes accessed"),
            "collective": coll, "compile_s": t_compile}


# ---------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--gp", action="store_true")
    ap.add_argument("--skip-probe", action="store_true")
    ap.add_argument("--moe-dispatch", default=None,
                    help="override cfg.moe_dispatch (perf variants)")
    ap.add_argument("--ring-cache", action="store_true",
                    help="ring-buffer windowed KV caches (perf variant)")
    ap.add_argument("--serve-bf16", action="store_true",
                    help="bf16 weights for decode cells (perf variant)")
    ap.add_argument("--prefill-last", action="store_true",
                    help="last-position-only prefill logits (perf variant)")
    ap.add_argument("--suffix", default="",
                    help="output-name suffix for variant cells")
    ap.add_argument("--out", default=str(OUT_DIR))
    args = ap.parse_args()
    overrides = ({"moe_dispatch": args.moe_dispatch}
                 if args.moe_dispatch else None)
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    def write(name, rec):
        with open(out / f"{name}.json", "w") as f:
            json.dump(rec, f, indent=1)

    if args.gp:
        for method in ("ppitc", "ppic", "picf"):
            for mesh_name in ("single", "multi"):
                name = f"gp_{method}_{mesh_name}"
                try:
                    rec = run_gp_cell(method, mesh_name)
                    rec["status"] = "ok"
                except Exception as e:
                    rec = {"status": "fail", "error": str(e),
                           "trace": traceback.format_exc()}
                print(name, rec.get("status"), flush=True)
                write(name, rec)
        return

    cells = []
    if args.all:
        for a in ARCH_NAMES:
            for sname in SHAPES:
                for mesh_name in ("single", "multi"):
                    cells.append((a, sname, mesh_name))
    else:
        cells.append((args.arch, args.shape, args.mesh))

    for arch, sname, mesh_name in cells:
        name = f"{arch}_{sname}_{mesh_name}{args.suffix}"
        if not applicable(arch, sname):
            write(name, {"status": "skip",
                         "reason": "long_500k needs sub-quadratic attention "
                                   "(DESIGN.md §shape-cell skips)"})
            print(name, "SKIP", flush=True)
            continue
        if (out / f"{name}.json").exists():
            rec = json.load(open(out / f"{name}.json"))
            if rec.get("status") == "ok":
                print(name, "CACHED", flush=True)
                continue
        t0 = time.time()
        try:
            rec = run_cell(arch, sname, mesh_name,
                           skip_probe=args.skip_probe, overrides=overrides,
                           ring_cache=args.ring_cache,
                           serve_bf16=args.serve_bf16,
                           last_logits=args.prefill_last)
            rec["status"] = "ok"
            print(f"{name} OK compile={rec['compile_s']:.1f}s "
                  f"bottleneck={rec['bottleneck']} "
                  f"roofline={rec['roofline_fraction']:.3f}", flush=True)
        except Exception as e:
            rec = {"status": "fail", "error": str(e)[-4000:],
                   "trace": traceback.format_exc()[-8000:]}
            print(name, "FAIL", str(e)[:300], flush=True)
        rec["wall_s"] = time.time() - t0
        write(name, rec)


if __name__ == "__main__":
    main()
