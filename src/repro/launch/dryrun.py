import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination on placeholder devices and extract memory / cost / roofline.

MUST be run as its own process (the two lines above lock jax to 512 host
devices before any other import).

Usage:
    python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import numpy as np

from repro.config import (get_config, INPUT_SHAPES, InputShape, ModelConfig,
                          SSM)
from repro.configs.input_shapes import input_specs
from repro.models import build_model
from repro.core.sfl import make_hasfl_train_step
from repro.dist.sharding import (state_shardings, batch_shardings,
                                 cache_shardings, make_shard_fn)
from repro.launch.mesh import make_production_mesh
from repro.launch import roofline as RL

SLIDING_WINDOW_500K = 8192

# perf-experiment knobs (overridden by launch/perf.py)
FORCE_REMAT = True
FORCE_ACCUM_SCALE = 1.0

# (arch, shape) combos that are skipped, with the DESIGN.md reason.
SKIPS = {
    ("whisper-medium", shape):
        "enc-dec audio model: its parameters are the edge simulator's unit "
        "list (conv stem, encoder layers, decoder; models/whisper.py), which "
        "the stacked SPMD split does not cut; and a 500k-token decode is "
        "meaningless for it (30s windows, 448-token decoder context)"
    for shape in INPUT_SHAPES
}


def variant_config(cfg: ModelConfig, shape: InputShape) -> ModelConfig:
    """long_500k needs sub-quadratic attention: dense/moe/vlm archs get an
    explicit sliding-window variant; hybrid gets windowed attn layers; SSM
    runs natively."""
    if shape.name == "long_500k" and cfg.family != SSM and cfg.sliding_window == 0:
        return dataclasses.replace(cfg, sliding_window=SLIDING_WINDOW_500K)
    return cfg


def choose_cut_reps(cfg: ModelConfig, n_clients: int, repeats: int) -> int:
    """Client-side prefix depth for the SPMD dry-run.

    The prefix is replicated per client, so its parameter bytes are
    multiplied by N.  Pick the deepest cut whose replicated prefix stays
    under ~25% of the server-side params; for expert-dense models (llama4)
    that is cut 0 — client keeps only the embedding, exactly what the
    paper's memory constraint C4 forces for edge devices that cannot hold
    expert layers."""
    total = cfg.param_count()
    per_rep = (total - 2 * cfg.vocab_size * cfg.d_model) / max(repeats, 1)
    # budget: replicated prefix (params + bf16 adam moments, 6 B/param)
    # may cost at most ~1 GB/device on the 256-chip pod
    budget_params = 1e9 * 256 / (n_clients * 6)
    best = 0
    for c in range(0, max(1, repeats // 8) + 1):
        prefix = cfg.vocab_size * cfg.d_model + c * per_rep
        if prefix <= budget_params:
            best = c
    return best


def _client_batch_specs(specs: dict, n_clients: int) -> dict:
    """Reshape [B, ...] data specs to [N, B/N, ...] for the HASFL step."""
    out = {}
    for k, s in specs.items():
        b = s.shape[0]
        assert b % n_clients == 0, (k, s.shape, n_clients)
        out[k] = jax.ShapeDtypeStruct((n_clients, b // n_clients) + s.shape[1:],
                                      s.dtype)
    return out


def build_train_combo(cfg: ModelConfig, shape: InputShape, mesh, *,
                      grad_accum: int = 4, optimizer_dtype: str = None,
                      unroll: bool = False):
    """The HASFL SPMD train step (paper technique) for this mesh.

    ``unroll=True`` builds the *cost variant*: layer scan unrolled and
    grad_accum=1 (same total FLOPs, loop-free HLO) so cost_analysis and
    the collective parse see every op.
    """
    model = build_model(cfg)
    dp = int(np.prod([mesh.shape[a] for a in mesh.axis_names
                      if a in ("pod", "data")]))
    n_clients = dp
    b_client = shape.global_batch // n_clients
    accum = 1 if unroll else max(1, int(grad_accum * FORCE_ACCUM_SCALE))
    while b_client % accum:
        accum -= 1
    from repro.models.transformer import layer_program
    _, repeats = layer_program(cfg)
    cut_reps = choose_cut_reps(cfg, n_clients, repeats)
    opt_dtype = optimizer_dtype or (
        "bfloat16" if cfg.param_count() > 1e11 else "float32")
    # 300B+: momentum (1 moment) instead of Adam (2) — the remaining
    # headroom on v5e; documented in EXPERIMENTS.md
    opt_name = "momentum" if cfg.param_count() > 3e11 else "adam"
    if accum > 1 and cfg.param_count() > 1e11:
        # 100B+ models need deeper accumulation to fit activations
        for cand in (16, 8):
            if b_client % cand == 0:
                accum = max(accum, cand)
                break
    # two-phase: shapes first, so the step can constrain grads to the
    # exact parameter shardings
    init_probe, _ = make_hasfl_train_step(
        model, n_clients=n_clients, cut_reps=cut_reps, agg_interval=15,
        optimizer_name=opt_name, lr=1e-4, optimizer_dtype=opt_dtype)
    state_structs = jax.eval_shape(init_probe, jax.random.PRNGKey(0))
    state_sh = state_shardings(state_structs, mesh)
    # NOTE: rep-level weight constraints (make_rep_shard_fn) were measured
    # to trigger "involuntary full rematerialization" resharding in GSPMD
    # without reducing peak memory — keep them off here.
    init_state, train_step = make_hasfl_train_step(
        model, n_clients=n_clients, cut_reps=cut_reps,
        agg_interval=15, optimizer_name=opt_name, lr=1e-4,
        optimizer_dtype=opt_dtype, grad_accum=accum, remat=FORCE_REMAT,
        shard_fn=make_shard_fn(mesh), unroll=unroll,
        param_shardings=(state_sh["client"], state_sh["server"]))
    batch_structs = _client_batch_specs(input_specs(cfg, shape), n_clients)
    in_sh = (state_sh, batch_shardings(batch_structs, mesh))
    meta = {"n_clients": n_clients, "b_client": b_client,
            "grad_accum": accum, "cut_reps": cut_reps,
            "optimizer_dtype": opt_dtype, "optimizer": opt_name}
    return train_step, (state_structs, batch_structs), in_sh, meta


def build_prefill_combo(cfg: ModelConfig, shape: InputShape, mesh,
                        unroll: bool = False):
    model = build_model(cfg)
    window = cfg.sliding_window or None

    def prefill_fn(params, batch):
        return model.prefill(params, batch, cache_len=min(
            shape.seq_len, window or shape.seq_len), window=window,
            unroll=unroll)

    params_structs = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch_structs = input_specs(cfg, shape)
    in_sh = (state_shardings(params_structs, mesh),
             batch_shardings(batch_structs, mesh))
    return prefill_fn, (params_structs, batch_structs), in_sh, {}


def build_decode_combo(cfg: ModelConfig, shape: InputShape, mesh,
                       unroll: bool = False):
    model = build_model(cfg)
    window = cfg.sliding_window or None
    cache_len = min(shape.seq_len, window or shape.seq_len)

    def decode_fn(params, cache, batch):
        return model.decode_step(params, cache, batch, window=window,
                                 unroll=unroll)

    params_structs = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache_structs = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, cache_len,
                                 window=window))
    batch_structs = input_specs(cfg, shape)
    in_sh = (state_shardings(params_structs, mesh),
             cache_shardings(cache_structs, mesh),
             batch_shardings(batch_structs, mesh))
    return decode_fn, (params_structs, cache_structs, batch_structs), in_sh, \
        {"cache_len": cache_len, "window": window or 0}


def run_combo(arch: str, shape_name: str, multi_pod: bool,
              with_cost: bool = True) -> dict:
    shape = INPUT_SHAPES[shape_name]
    if (arch, shape_name) in SKIPS:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": SKIPS[(arch, shape_name)]}
    cfg = variant_config(get_config(arch), shape)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))

    def build(unroll):
        if shape.kind == "train":
            return build_train_combo(cfg, shape, mesh, unroll=unroll)
        if shape.kind == "prefill":
            return build_prefill_combo(cfg, shape, mesh, unroll=unroll)
        return build_decode_combo(cfg, shape, mesh, unroll=unroll)

    # --- pass 1: scanned variant — the compile/memory proof ---------------
    t0 = time.time()
    with mesh:
        fn, args, in_sh, meta = build(unroll=False)
        out_sh = (in_sh[0], None) if shape.kind == "train" else None
        # donation: train donates the state (params+opt update in place);
        # decode donates the KV/state cache — without it the dry-run
        # double-buffers the cache (measured +6.4 GB/device on phi3
        # decode_32k)
        if shape.kind == "train":
            donate = (0,)
        elif shape.kind == "decode":
            donate = (1,)
        else:
            donate = ()
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    mem_info = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes"):
        if hasattr(mem, attr):
            mem_info[attr] = int(getattr(mem, attr))
    print("memory_analysis:", mem_info, flush=True)

    # --- pass 2: unrolled cost variant — roofline terms --------------------
    cost_source = "unrolled"
    t1 = time.time()
    if not with_cost:
        cost_source = "scanned (loop bodies counted once — lower bound)"
    try:
        if not with_cost:
            raise RuntimeError("cost variant disabled (--no-cost)")
        with mesh:
            fn_u, args_u, in_sh_u, _ = build(unroll=True)
            out_sh_u = (in_sh_u[0], None) if shape.kind == "train" else None
            compiled_u = jax.jit(fn_u, in_shardings=in_sh_u,
                                 out_shardings=out_sh_u) \
                .lower(*args_u).compile()
        hlo = compiled_u.as_text()
        rf = RL.analyze(compiled_u, hlo, chips,
                        model_flops=RL.analytic_model_flops(cfg, shape))
    except Exception as e:  # noqa: BLE001
        print("cost variant failed (%r); falling back to scanned HLO" % e,
              flush=True)
        cost_source = "scanned (loop bodies counted once — lower bound)"
        hlo = compiled.as_text()
        rf = RL.analyze(compiled, hlo, chips,
                        model_flops=RL.analytic_model_flops(cfg, shape))
    t_cost = time.time() - t1
    print("cost_analysis(%s): flops=%.3e bytes=%.3e coll=%.3e" %
          (cost_source, rf.flops, rf.hbm_bytes, rf.collective_bytes),
          flush=True)

    per_dev_bytes = (mem_info.get("argument_size_in_bytes", 0)
                     + mem_info.get("temp_size_in_bytes", 0)
                     + mem_info.get("output_size_in_bytes", 0)
                     - 2 * mem_info.get("alias_size_in_bytes", 0))
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "chips": chips, "status": "ok",
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "cost_compile_s": round(t_cost, 1), "cost_source": cost_source,
        "memory": mem_info, "per_device_bytes": per_dev_bytes,
        "fits_v5e_16g": bool(per_dev_bytes < 16e9),
        "roofline": rf.summary(),
        "collectives": {"bytes_by_op": rf.collectives.bytes_by_op,
                        "count_by_op": rf.collectives.count_by_op},
        **meta,
    }
    return rec


ASSIGNED = [
    "llama4-maverick-400b-a17b", "phi3-mini-3.8b", "glm4-9b",
    "whisper-medium", "xlstm-350m", "smollm-135m", "internvl2-1b",
    "dbrx-132b", "jamba-v0.1-52b", "qwen3-1.7b",
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--no-cost", action="store_true",
                    help="skip the unrolled cost variant (multi-pod pass "
                         "only needs the compile/memory proof)")
    args = ap.parse_args()
    from repro.utils.cache import enable_compilation_cache
    enable_compilation_cache()

    archs = ASSIGNED if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape_name in shapes:
            for multi in meshes:
                tag = f"{arch}_{shape_name}_{'multi' if multi else 'single'}"
                out_path = os.path.join(args.out, tag + ".json")
                if os.path.exists(out_path):
                    print(f"[skip existing] {tag}", flush=True)
                    continue
                print(f"=== {tag} ===", flush=True)
                try:
                    cfgc = get_config(arch)
                    kind = INPUT_SHAPES[shape_name].kind
                    auto_cost = (cfgc.param_count() < 2e10
                                 or kind == "decode")
                    rec = run_combo(arch, shape_name, multi,
                                    with_cost=auto_cost
                                    and not args.no_cost)
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": "multi" if multi else "single",
                           "status": "error", "error": repr(e)}
                    failures += 1
                with open(out_path, "w") as f:
                    json.dump(rec, f, indent=1)
                print(json.dumps({k: v for k, v in rec.items()
                                  if k not in ("memory", "collectives")},
                                 indent=1), flush=True)
    print(f"done; failures={failures}", flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
