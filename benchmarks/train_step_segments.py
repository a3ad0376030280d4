"""Segment-level timing of the ResNet-50 train step on the real chip.

Breaks the step time into segments — forward (eval/train mode),
forward+backward, full step (fwd+bwd+update) — plus XLA's cost analysis
(flops, bytes) for the compiled step, to locate where time goes before
reaching for flags or kernels.  Companion to bench.py (which records the
single headline number).

One process, which holds the chip while it runs: run nothing else on
the chip beside it.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def timeit(fn, *args, n=20, warmup=3):
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main():
    import fluxdistributed_tpu as fd
    from fluxdistributed_tpu import optim, sharding
    from fluxdistributed_tpu.models import resnet50
    from fluxdistributed_tpu.parallel import TrainState, make_train_step
    from fluxdistributed_tpu.parallel.dp import flax_loss_fn

    dev = jax.devices()[0]
    print(f"device: {dev}, platform {dev.platform}")

    # --- 1. matmul peak as dispatched from this host -------------------
    k = 8192
    a = jnp.asarray(np.random.default_rng(0).normal(0, 1, (k, k)), jnp.bfloat16)
    b = jnp.asarray(np.random.default_rng(1).normal(0, 1, (k, k)), jnp.bfloat16)

    @jax.jit
    def mm(a, b):
        return a @ b

    dt = timeit(mm, a, b)
    print(f"matmul {k}^3 bf16: {dt*1e3:.2f} ms -> {2*k**3/dt/1e12:.1f} TFLOP/s")

    # --- 2. ResNet-50 segments -----------------------------------------
    batch = 256
    mesh = fd.data_mesh()
    model = resnet50(num_classes=1000)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (batch, 224, 224, 3)), jnp.bfloat16)
    y = jnp.asarray(np.asarray(fd.onehot(rng.integers(0, 1000, batch), 1000)))

    variables = model.init(jax.random.PRNGKey(0), x[:1], train=True)
    params = variables["params"]
    mstate = {k2: v for k2, v in variables.items() if k2 != "params"}

    # fwd eval mode (no BN stats update)
    @jax.jit
    def fwd_eval(params, mstate, x):
        return model.apply({"params": params, **mstate}, x, train=False)

    print(f"fwd (eval mode):  {timeit(fwd_eval, params, mstate, x)*1e3:.2f} ms")

    # fwd train mode (BN batch stats)
    @jax.jit
    def fwd_train(params, mstate, x):
        out, mut = model.apply(
            {"params": params, **mstate}, x, train=True,
            mutable=list(mstate.keys()),
        )
        return out

    print(f"fwd (train mode): {timeit(fwd_train, params, mstate, x)*1e3:.2f} ms")

    # fwd+bwd
    loss_fn = flax_loss_fn(model, fd.logitcrossentropy)

    @jax.jit
    def fwdbwd(params, mstate, x, y):
        def lf(p):
            return loss_fn(p, mstate, {"image": x, "label": y}, True)

        (l, _), g = jax.value_and_grad(lf, has_aux=True)(params)
        return l, g

    print(f"fwd+bwd:          {timeit(fwdbwd, params, mstate, x, y)*1e3:.2f} ms")

    # full step
    opt = optim.momentum(0.1, 0.9)
    step = make_train_step(loss_fn, opt, mesh, donate=False)
    state = TrainState.create(
        sharding.replicate(params, mesh), opt,
        model_state=sharding.replicate(mstate, mesh),
    )
    bt = {"image": x, "label": y}
    dt = timeit(lambda s: step(s, bt)[0], state, n=10)
    print(f"full step:        {dt*1e3:.2f} ms  ({batch/dt:.0f} img/s)")

    # cost analysis
    lowered = jax.jit(lambda s, b: step(s, b)).lower(state, bt)
    comp = lowered.compile()
    ca = comp.cost_analysis()
    if ca:
        d = ca[0] if isinstance(ca, (list, tuple)) else ca
        fl = d.get("flops", 0)
        bytes_ = d.get("bytes accessed", 0)
        print(f"cost_analysis: flops={fl/1e12:.2f} TFLOP, bytes={bytes_/1e9:.1f} GB")
        print(f"  -> flops/img = {fl/batch/1e9:.1f} GFLOP")
        print(f"  -> at measured step: {fl/dt/1e12:.0f} TFLOP/s achieved")
        print(f"  -> HBM bw needed: {bytes_/dt/1e9:.0f} GB/s")


if __name__ == "__main__":
    main()
