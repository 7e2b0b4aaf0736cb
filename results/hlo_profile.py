"""Dry-run HLO "profiler": compile one target and print the largest ops /
tensors from the post-optimisation HLO, without running anything.

Two targets:

* LLM configs (the original mode) — compile one (arch × shape) on the
  512-placeholder-device production mesh and print the largest
  collectives:

    python results/hlo_profile.py --arch gpt_125m --shape train_4k

* the HFL round engine — compile the jitted ``round_step`` at an N×M
  size and print the largest ops/tensors by result bytes (the
  ``jax.named_scope`` stage names from ``repro.telemetry.spans`` show up
  in the op_name column, so every big tensor is attributable to
  associate/allocate/schedule/train/eval):

    python results/hlo_profile.py --round-engine 1024x16
    python results/hlo_profile.py --round-engine 4096x32 --candidates 8
    python results/hlo_profile.py --round-engine 1024x16 --telemetry

The arg parse happens BEFORE jax imports: the LLM mode needs
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` set first, and
the round-engine mode must NOT see it (a 512-way CPU "mesh" would just
slow the single-program compile down).
"""
import argparse
import os
import re
import sys

sys.path.insert(0, "src")

ap = argparse.ArgumentParser()
ap.add_argument("--arch", default=None, help="LLM mode: config name")
ap.add_argument("--shape", default=None, help="LLM mode: input shape name")
ap.add_argument("--unroll", action="store_true")
ap.add_argument("--top", type=int, default=15)
ap.add_argument("--round-engine", default=None, metavar="NxM",
                help="HFL mode: compile round_step at N clients x M edges "
                     "(e.g. 1024x16) and print its largest ops/tensors")
ap.add_argument("--candidates", type=int, default=None, metavar="K",
                help="HFL mode: (N, K) candidate frontier")
ap.add_argument("--telemetry", action="store_true",
                help="HFL mode: compile with EngineSpec(telemetry=True)")
args = ap.parse_args()

if args.round_engine is None:
    if not (args.arch and args.shape):
        ap.error("either --arch + --shape (LLM mode) or --round-engine NxM")
    # the LLM dry-run wants the placeholder device farm; must be set
    # before jax initialises its backends
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import jax
import jax.numpy as jnp

from repro.launch.roofline import _shape_bytes, _group_size

_SHAPE_RE = (r"((?:\([^)]*\)|[a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?))")


def _print_cost(compiled):
    ca = compiled.cost_analysis()
    print("flops/device:", ca.get("flops"), " bytes/device:",
          ca.get("bytes accessed"))


def round_engine_main() -> None:
    import dataclasses

    from repro.configs.hfl_mnist import CONFIG
    from repro.core import engine

    try:
        n, m = (int(v) for v in args.round_engine.lower().split("x"))
    except ValueError:
        raise SystemExit("--round-engine expects NxM, e.g. 1024x16")
    cfg = dataclasses.replace(CONFIG, n_clients=n, n_edges=m,
                              clients_per_edge=4, min_samples=60,
                              max_samples=120, hidden=16, input_dim=32,
                              local_batch=16)
    spec = engine.EngineSpec(policy="gcea", scheduler="fastest",
                             candidates_k=args.candidates,
                             telemetry=args.telemetry)
    state, bundle, _ = engine.init_simulation(cfg, seed=0)
    compiled = jax.jit(engine.round_step, static_argnums=(0, 1)).lower(
        cfg, spec, state, bundle).compile()
    text = compiled.as_text()
    # every HLO op with its result shape; rank by result bytes
    pat = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*" + _SHAPE_RE
                     + r"\s+([\w\-]+)")
    rows = []
    for line in text.splitlines():
        mm = pat.match(line)
        if not mm:
            continue
        op = mm.group(2)
        if op in ("parameter", "constant", "tuple", "get-tuple-element"):
            continue
        meta = re.search(r'op_name="([^"]*)"', line)
        rows.append((_shape_bytes(mm.group(1)), op,
                     (meta.group(1) if meta else "")[-90:]))
    rows.sort(key=lambda r: (-r[0], r[1]))
    print(f"== round_step {n}x{m} "
          f"(candidates_k={args.candidates}, telemetry={args.telemetry}): "
          f"top {args.top} ops by result bytes ==")
    for nbytes, op, name in rows[:args.top]:
        print(f"{nbytes/1e6:10.3f} MB  {op:<24} {name}")
    print(f"total ops: {len(rows)}")
    _print_cost(compiled)


def llm_main() -> None:
    from repro.configs import INPUT_SHAPES, get_config, input_specs
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import make_prefill_step, make_serve_step, \
        make_train_step
    from repro.sharding import input_shardings, param_shardings

    cfg = get_config(args.arch)
    if args.unroll:
        cfg = cfg.replace(scan_layers=False)
    shape = INPUT_SHAPES[args.shape]
    mesh = make_production_mesh()
    specs = input_specs(cfg, shape)
    in_sh = input_shardings(specs, mesh, shape.global_batch)

    with mesh:
        if shape.kind == "train":
            step_fn, model, _ = make_train_step(cfg)
            p_shapes = jax.eval_shape(model.init, jax.random.key(0))
            p_sh = param_shardings(p_shapes, mesh)
            o_sh = {"m": p_sh, "v": p_sh}
            fn = jax.jit(step_fn, in_shardings=(p_sh, o_sh, None, in_sh),
                         out_shardings=(p_sh, o_sh, None, None))
            compiled = fn.lower(p_shapes, {"m": p_shapes, "v": p_shapes},
                                jax.ShapeDtypeStruct((), jnp.int32),
                                specs).compile()
        elif shape.kind == "prefill":
            step_fn, model = make_prefill_step(cfg)
            p_shapes = jax.eval_shape(model.init, jax.random.key(0))
            p_sh = param_shardings(p_shapes, mesh)
            compiled = jax.jit(step_fn, in_shardings=(p_sh, in_sh)).lower(
                p_shapes, specs).compile()
        else:
            step_fn, model = make_serve_step(cfg)
            p_shapes = jax.eval_shape(model.init, jax.random.key(0))
            p_sh = param_shardings(p_shapes, mesh)
            fn = jax.jit(step_fn,
                         in_shardings=(p_sh, in_sh["token"],
                                       in_sh["cache"], in_sh["index"]),
                         out_shardings=(in_sh["token"], in_sh["cache"]))
            compiled = fn.lower(p_shapes, specs["token"], specs["cache"],
                                specs["index"]).compile()

    text = compiled.as_text()
    rows = []
    for line in text.splitlines():
        m = re.search(r"=\s*" + _SHAPE_RE + r"\s+"
                      r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                      r"collective-permute)(-start)?\(", line)
        if not m:
            continue
        nbytes = _shape_bytes(m.group(1))
        g = _group_size(line)
        meta = re.search(r'op_name="([^"]*)"', line)
        rows.append((nbytes, m.group(2), g,
                     (meta.group(1) if meta else "")[-110:]))
    rows.sort(reverse=True)
    print(f"== top {args.top} collectives (result bytes, kind, group) ==")
    for nbytes, kind, g, name in rows[:args.top]:
        print(f"{nbytes/1e9:9.3f} GB  {kind:<19} g={g:<4} {name}")
    print(f"total collective ops: {len(rows)}")
    _print_cost(compiled)


if args.round_engine is not None:
    round_engine_main()
else:
    llm_main()
