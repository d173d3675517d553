"""Compile-only rehearsal for a train cell across chips, without the chip:
`JAX_PLATFORMS=cpu python3 -m benchmarks.rehearse_compile gptj-6b.train-fsdp4
[depth,batch_per_chip ...]`, where depth is a value for the first key the
configuration lists under `reduced` (`n_layer` there).

Compiles the cell's real train step for `v5e:2x2` as described (not attached)
devices, with the cell's mesh, shardings and optimizer, and prints the bytes
each device needs. It is how depth and batch were picked before any
four-chip minute was spent. Nothing runs: a compile that passes is not a
chip run."""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def compile_step(cell_name: str, depth=None, batch_per_chip=None) -> dict:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models.gpt import CONFIGS, init_params, make_train_step, param_shardings
    from ray_tpu.parallel import make_mesh

    from ray_tpu.ops import attention

    from . import harness

    # The program picks its kernels by `jax.default_backend()`, which is the
    # CPU here: steer it to the TPU branch, in this script and nowhere else.
    attention._on_tpu = lambda: True
    loaded = harness.load_cell(cell_name)
    config, mix, cell = loaded["config"], loaded["traffic"], loaded["cell"]
    part = config["runners"]["train"]
    if depth is not None:
        if not config["reduced"]:
            raise SystemExit(f"{cell_name}: its configuration reduces no key to vary")
        config = {**config, config["reduced"][0]: depth}
    bpc = batch_per_chip or mix["batch_per_chip"]
    arch = harness.arch(config["arch"])
    m = arch.dims(config, False)
    model, overrides = arch.program(config, m)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    devices = list(topo.devices)[: cell["chips"]]
    mesh = make_mesh(devices, **part["mesh"])
    cfg = CONFIGS[model](
        **{**overrides, "max_seq": mix["seq"]}, attn_impl=part["attn_impl"], remat=True,
        remat_policy=part["remat_policy"])
    shardings = param_shardings(cfg, mesh)
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    params = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=shardings[k])
              for k, v in shapes.items()}
    o = part["optimizer"]
    opt = optax.adamw(o["lr"], weight_decay=o["weight_decay"],
                      mu_dtype=getattr(jnp, o["mu_dtype"]))
    opt_shapes = jax.eval_shape(opt.init, shapes)
    repl = NamedSharding(mesh, P())

    def place(path, leaf):
        name = next((getattr(p, "key", None) for p in reversed(path)
                     if getattr(p, "key", None) in shardings), None)
        sh = shardings[name] if name and leaf.shape == shapes[name].shape else repl
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=sh)

    opt_state = jax.tree_util.tree_map_with_path(place, opt_shapes)
    batch = bpc * len(devices)
    tokens = jax.ShapeDtypeStruct(
        (batch, mix["seq"] + 1), jnp.int32,
        sharding=NamedSharding(mesh, P(("dp", "fsdp"), None)))
    step = jax.jit(make_train_step(cfg, opt, mesh=mesh), donate_argnums=(0,))
    compiled = step.lower((params, opt_state), {"tokens": tokens}).compile()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    return {
        **{k: config[k] for k in config["reduced"][:1]},
        "global_batch": batch, "seq": mix["seq"],
        "params_B": sum(v.size for v in shapes.values()) / 1e9,
        "per_device_GiB": {
            "arguments": mem.argument_size_in_bytes / 2**30,
            "temp": mem.temp_size_in_bytes / 2**30,
            "output": mem.output_size_in_bytes / 2**30,
            "alias": mem.alias_size_in_bytes / 2**30,
        },
        "mosaic_calls": hlo.count("tpu_custom_call"),
        "collectives": {k: hlo.count(k + "(") + hlo.count(k + "-start(") for k in
                        ("all-gather", "reduce-scatter", "all-reduce")},
    }


def main() -> int:
    cell = sys.argv[1]
    tries = [tuple(int(x) for x in a.split(",")) for a in sys.argv[2:]] or [(None, None)]
    for depth, bpc in tries:
        try:
            print(compile_step(cell, depth, bpc), flush=True)
        except Exception as e:  # noqa: BLE001 — a refusal is the answer
            print({"depth": depth, "batch_per_chip": bpc,
                   "refused": str(e).splitlines()[0][:300]}, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
