"""Compile-time calibration: lower+compile the heaviest cell
(deepseek-v3-671b × train_4k × 512-chip mesh), exec + cost variants.
Run:  PYTHONPATH=src python -m repro.launch.calibrate_compile [cost]
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import sys  # noqa: E402
import time  # noqa: E402
t0 = time.time()

import dataclasses  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import deepseek_v3_671b  # noqa: E402
from repro.launch.mesh import make_production_mesh, dp_axes  # noqa: E402
from repro.models.transformer import init_params, lm_loss, param_pspecs  # noqa: E402
from repro.train.optimizer import (  # noqa: E402
    OptimizerConfig, adafactor_state_pspecs, clip_by_global_norm,
    make_optimizer,
)


def main():
    cost_variant = "cost" in sys.argv[1:]
    cfg = deepseek_v3_671b.config()
    if cost_variant:
        cfg = dataclasses.replace(cfg, scan_unroll=True, attn_block_k=4096,
                                  remat=False)
    mesh = make_production_mesh(multi_pod=True)
    dp = dp_axes(mesh)
    print(f"mesh={mesh.shape} dp={dp} cost_variant={cost_variant} "
          f"import: {time.time()-t0:.1f}s")

    opt_cfg = OptimizerConfig(name="adafactor", lr=1e-4, weight_decay=0.0)
    opt_init, opt_update = make_optimizer(opt_cfg)

    def train_step(params, opt_state, tokens, labels):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: lm_loss(p, cfg, tokens, labels, mesh=mesh, dp_axes=dp),
            has_aux=True,
        )(params)
        grads, gnorm = clip_by_global_norm(grads, 1.0)
        new_params, new_state = opt_update(grads, opt_state, params,
                                           jnp.float32(1e-4))
        return new_params, new_state, loss, gnorm

    params_shape = jax.eval_shape(lambda k: init_params(k, cfg),
                                  jax.random.key(0))
    opt_shape = jax.eval_shape(opt_init, params_shape)
    pspecs = param_pspecs(cfg)
    opt_pspecs = adafactor_state_pspecs(pspecs, params_shape, opt_cfg)

    as_abs = lambda shapes, specs: jax.tree.map(
        lambda sh, spec: jax.ShapeDtypeStruct(
            sh.shape, sh.dtype, sharding=NamedSharding(mesh, spec)
        ),
        shapes, specs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )
    params_abs = as_abs(params_shape, pspecs)
    opt_abs = as_abs(opt_shape, opt_pspecs)

    b, s = 256, 4096
    tok = jax.ShapeDtypeStruct(
        (b, s), jnp.int32, sharding=NamedSharding(mesh, P(dp, None))
    )

    t1 = time.time()
    jitted = jax.jit(train_step, donate_argnums=(0, 1))
    with jax.set_mesh(mesh):
        lowered = jitted.lower(params_abs, opt_abs, tok, tok)
        t2 = time.time()
        print(f"lower: {t2-t1:.1f}s")
        compiled = lowered.compile()
    t3 = time.time()
    print(f"compile: {t3-t2:.1f}s")
    mem = compiled.memory_analysis()
    gib = 1 << 30
    print(f"per-device: args {mem.argument_size_in_bytes/gib:.2f} GiB, "
          f"out {mem.output_size_in_bytes/gib:.2f} GiB, "
          f"temp {mem.temp_size_in_bytes/gib:.2f} GiB, "
          f"alias {mem.alias_size_in_bytes/gib:.2f} GiB")
    cost = compiled.cost_analysis()
    print("flops:", cost.get("flops"), "bytes:", cost.get("bytes accessed"))
    print(f"TOTAL {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
