"""Cold-start subsystem (fluxdistributed_tpu.compilation).

Fast tier: topology fingerprinting, the serialize→deserialize round
trip of AOT executables, the load-or-compile fallback on fingerprint
mismatch, engine prewarm/AOT invariants, and the trainer's
``cache_dir``/``aot``/``warmup`` wiring — all on the 8-device fake CPU
mesh.  Slow tier: the headline demonstration — a SECOND process
pointed at a warm persistent cache registers ZERO compilation-cache
misses (every XLA compile served from disk).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fluxdistributed_tpu import compilation
from fluxdistributed_tpu.obs import get_registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- fingerprint


def test_topology_fingerprint_stable_and_tag_sensitive():
    a, b = compilation.topology_fingerprint(), compilation.topology_fingerprint()
    assert a == b and len(a) == 16
    assert compilation.topology_fingerprint(tag="zero1") != a
    from fluxdistributed_tpu.mesh import data_mesh

    assert compilation.topology_fingerprint(mesh=data_mesh()) != a


def test_abstract_signature_tracks_shapes_and_structure():
    x, y = jnp.ones((4, 4)), jnp.ones((8, 4))
    assert (compilation.abstract_signature((x,))
            == compilation.abstract_signature((jnp.zeros((4, 4)),)))
    assert (compilation.abstract_signature((x,))
            != compilation.abstract_signature((y,)))
    assert (compilation.abstract_signature(({"a": x},))
            != compilation.abstract_signature(({"b": x},)))
    assert (compilation.abstract_signature((x,))
            != compilation.abstract_signature((x.astype(jnp.bfloat16),)))


# ------------------------------------------------------------ cache enablement


@pytest.fixture
def restore_cache_config(monkeypatch):
    """Cache tests start from "variable unset" and leave the process's
    cache config as they found it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.delenv(compilation.CACHE_DIR_ENV, raising=False)
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    cc.reset_cache()  # drop the memoized cache-in-use decision
    compilation._cache_dir = None


@pytest.mark.parametrize("env, explicit, want", [
    # the variable wins over everything, verbatim
    ("/x", None, "/x"),
    ("/x", "/elsewhere", "/x"),
    # unset: an explicit --compile-cache DIR
    (None, "/elsewhere", "/elsewhere"),
    ("", "/elsewhere", "/elsewhere"),
    # neither: the ONE fixed in-checkout path
    (None, None, os.path.join(REPO, ".jax_cache")),
])
def test_resolve_cache_dir_rule(monkeypatch, env, explicit, want):
    if env is None:
        monkeypatch.delenv(compilation.CACHE_DIR_ENV, raising=False)
    else:
        monkeypatch.setenv(compilation.CACHE_DIR_ENV, env)
    assert compilation.resolve_cache_dir(explicit) == want
    # fixed: asking twice (another process, another time) agrees
    assert compilation.resolve_cache_dir(explicit) == want


def test_default_cache_dir_is_gitignored():
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_enable_persistent_cache(tmp_path, restore_cache_config):
    resolved = compilation.enable_persistent_cache(str(tmp_path / "cc"))
    # exactly the directory asked for: no sub-directory appended
    assert resolved == str(tmp_path / "cc") and os.path.isdir(resolved)
    assert jax.config.jax_compilation_cache_dir == resolved
    assert compilation.persistent_cache_dir() == resolved
    assert get_registry().value("fdtpu_compile_cache_enabled") == 1
    # thresholds: cache everything
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1


def test_enable_persistent_cache_sets_no_dir_when_env_set(
        tmp_path, restore_cache_config, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set the program keeps its cache
    exactly there and sets no directory in code: jax read the variable
    at import, so the config value is left as it stands."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compilation.CACHE_DIR_ENV, str(tmp_path / "env"))
    resolved = compilation.enable_persistent_cache(str(tmp_path / "flag"))
    assert resolved == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before
    assert not os.path.exists(tmp_path / "flag")


# ------------------------------------------------------------------ AOT files


def test_aot_serialize_deserialize_round_trip(tmp_path):
    f = jax.jit(lambda x, y: {"out": x @ y + 1.0})
    x = jnp.ones((8, 8))
    compiled = compilation.aot_compile(f, x, x)
    path = str(tmp_path / "f.jaxexec")
    compilation.save_executable(path, compiled)
    loaded = compilation.load_executable(path)
    assert loaded is not None
    np.testing.assert_allclose(loaded(x, x)["out"], compiled(x, x)["out"])


def test_load_executable_rejects_mismatch_and_corruption(tmp_path):
    f = jax.jit(lambda x: x * 2)
    x = jnp.ones((4,))
    path = str(tmp_path / "f.jaxexec")
    compilation.save_executable(
        path, compilation.aot_compile(f, x), fingerprint="not-this-topology")
    assert compilation.load_executable(path) is None  # fingerprint mismatch
    with open(path, "wb") as fh:
        fh.write(b"garbage")
    assert compilation.load_executable(path) is None  # corrupt
    assert compilation.load_executable(str(tmp_path / "missing")) is None


def test_load_or_compile_falls_back_then_reuses(tmp_path):
    f = jax.jit(lambda x: jnp.sum(x * 3))
    x = jnp.arange(16.0)
    reg = get_registry()
    c0 = reg.value("fdtpu_aot_compiles_total")
    l0 = reg.value("fdtpu_aot_loads_total")
    a = compilation.load_or_compile(f, (x,), directory=str(tmp_path), name="s")
    assert reg.value("fdtpu_aot_compiles_total") == c0 + 1
    b = compilation.load_or_compile(f, (x,), directory=str(tmp_path), name="s")
    assert reg.value("fdtpu_aot_loads_total") == l0 + 1
    assert float(a(x)) == float(b(x)) == float(jnp.sum(x * 3))
    # stamp the on-disk file with a foreign fingerprint: next call must
    # fall back to a fresh compile AND re-serialize for this topology
    fp = compilation.topology_fingerprint()
    sig = compilation.abstract_signature((x,))
    path = tmp_path / f"s-{fp}-{sig}{compilation.AOT_SUFFIX}"
    compilation.save_executable(
        str(path), compilation.aot_compile(f, x), fingerprint="stale")
    c1 = reg.value("fdtpu_aot_compiles_total")
    compilation.load_or_compile(f, (x,), directory=str(tmp_path), name="s")
    assert reg.value("fdtpu_aot_compiles_total") == c1 + 1
    l1 = reg.value("fdtpu_aot_loads_total")
    compilation.load_or_compile(f, (x,), directory=str(tmp_path), name="s")
    assert reg.value("fdtpu_aot_loads_total") == l1 + 1  # rewritten, loads now
    # a different argument signature selects a different file
    c2 = reg.value("fdtpu_aot_compiles_total")
    compilation.load_or_compile(
        f, (jnp.arange(8.0),), directory=str(tmp_path), name="s")
    assert reg.value("fdtpu_aot_compiles_total") == c2 + 1


def test_aot_compile_requires_jitted_callable():
    with pytest.raises(ValueError, match="lower"):
        compilation.aot_compile(lambda x: x, 1.0)


def test_callable_tag_sees_hyperparameters_not_addresses():
    """Two optimizers differing ONLY in a closed-over hyperparameter
    (identical program shapes) must tag differently; the same
    configuration must tag identically (no memory addresses)."""
    from fluxdistributed_tpu import optim

    a = compilation.callable_tag(optim.momentum(0.1, 0.9).update)
    b = compilation.callable_tag(optim.momentum(0.01, 0.9).update)
    c = compilation.callable_tag(optim.momentum(0.1, 0.9).update)
    assert a != b and a == c
    assert "0x" not in a  # address-free — stable across processes
    # schedules one level down are visible too
    sched = compilation.callable_tag(
        optim.momentum(optim.warmup_cosine(0.1, 5, 100)).update)
    assert sched != a


def test_config_tag_scrubs_addresses_and_digests_callables():
    """config_tag is THE AOT key builder: reprs carrying memory
    addresses (a model whose attn_fn prints '<function ... at 0x..>')
    must hash identically across processes, and two processes' different
    addresses must not change the key."""
    a = compilation.config_tag("attn_fn=<function core at 0x7f01>", 8)
    b = compilation.config_tag("attn_fn=<function core at 0x9e22>", 8)
    assert a == b and len(a) == 12
    assert compilation.config_tag("x", 8) != compilation.config_tag("x", 16)
    from fluxdistributed_tpu import optim

    assert (compilation.config_tag(optim.momentum(0.1).update)
            != compilation.config_tag(optim.momentum(0.2).update))


def test_prepare_training_aot_distinguishes_optimizers(tmp_path):
    """A changed learning rate must NOT load the previous run's
    serialized train step (the hyperparameter is a baked-in constant)."""
    from fluxdistributed_tpu import optim
    from fluxdistributed_tpu.data import SyntheticDataset
    from fluxdistributed_tpu.models import SimpleCNN
    from fluxdistributed_tpu.train import prepare_training

    def prep(opt):
        ds = SyntheticDataset(nsamples=64, nclasses=4, shape=(16, 16, 3))
        return prepare_training(SimpleCNN(num_classes=4), ds, opt,
                                batch_size=16, cycles=1, aot=str(tmp_path))

    reg = get_registry()
    c0 = reg.value("fdtpu_aot_compiles_total")
    prep(optim.momentum(0.1, 0.9))
    prep(optim.momentum(0.01, 0.9))  # different lr → different file
    assert reg.value("fdtpu_aot_compiles_total") == c0 + 2
    assert len(os.listdir(tmp_path)) == 2


# ------------------------------------------------------------- engine prewarm


def _tiny_lm():
    from fluxdistributed_tpu.models import lm_tiny

    model = lm_tiny(vocab=32, depth=2, dim=64, mlp_dim=128,
                    dtype=jnp.float32)
    params = model.init(
        jax.random.PRNGKey(0), np.zeros((1, 2), np.int32), train=False
    )["params"]
    return model, params


def _serve_all(engine, prompts, new=6):
    from fluxdistributed_tpu.serve import Request, Scheduler

    sched = Scheduler(engine, max_queue=16)
    reqs = [Request(prompt=p, max_new_tokens=new) for p in prompts]
    sched.generate_all(reqs)
    return [r.tokens for r in reqs]


def _ref_tokens(model, params, prompt, new):
    from fluxdistributed_tpu.models import generate

    dm = model.clone(decode=True)
    out = generate(dm, params, np.asarray([prompt], np.int32),
                   total_len=len(prompt) + new)
    return list(np.asarray(out)[0])


def test_engine_prewarm_prepays_every_compile():
    """prewarm=True compiles each bucket's prefill, the splice and the
    decode step BEFORE traffic; serving then adds zero compiles and
    keeps token-for-token parity — the ONE-decode-compile invariant
    with the compile moved ahead of the first request."""
    model, params = _tiny_lm()
    from fluxdistributed_tpu.serve import LMEngine

    engine = LMEngine(model, params, max_slots=3, max_len=32,
                      buckets=(4, 8), prewarm=True)
    warm = engine.compile_stats()
    if warm["decode_compiles"] < 0:
        pytest.skip("this jax cannot report jit cache sizes")
    assert warm["decode_compiles"] == 1
    assert warm["insert_compiles"] == 1
    assert warm["prefill_compiles"] == len(engine.buckets)
    prompts = [[1, 2, 3], [5, 6], [7, 1, 2, 3, 4]]
    got = _serve_all(engine, prompts)
    assert engine.compile_stats() == warm, "traffic recompiled a program"
    for tokens, p in zip(got, prompts):
        assert tokens == _ref_tokens(model, params, p, 6)


def test_engine_aot_pool_round_trip(tmp_path):
    """aot_dir engines serve through deserialized executables: engine 2
    loads engine 1's serialized pool (counted in the registry) and
    produces identical tokens."""
    model, params = _tiny_lm()
    from fluxdistributed_tpu.serve import LMEngine

    reg = get_registry()
    c0 = reg.value("fdtpu_aot_compiles_total")
    e1 = LMEngine(model, params, max_slots=2, max_len=32,
                  buckets=(4,), aot_dir=str(tmp_path))
    n_programs = len(e1._aot)
    assert n_programs == 5  # insert, step, sample1, prefill x {4, 32}
    assert reg.value("fdtpu_aot_compiles_total") == c0 + n_programs
    l0 = reg.value("fdtpu_aot_loads_total")
    e2 = LMEngine(model, params, max_slots=2, max_len=32,
                  buckets=(4,), aot_dir=str(tmp_path))
    assert reg.value("fdtpu_aot_loads_total") == l0 + n_programs
    assert e2.compile_stats()["aot_programs"] == n_programs
    prompts = [[1, 2], [3, 1, 4]]
    assert _serve_all(e1, prompts) == _serve_all(e2, prompts)
    for tokens, p in zip(_serve_all(e2, prompts), prompts):
        assert tokens == _ref_tokens(model, params, p, 6)


# ------------------------------------------------------------- trainer wiring


def _prepare(**kw):
    from fluxdistributed_tpu import optim
    from fluxdistributed_tpu.data import SyntheticDataset
    from fluxdistributed_tpu.models import SimpleCNN
    from fluxdistributed_tpu.train import prepare_training

    dataset = SyntheticDataset(nsamples=64, nclasses=4, shape=(16, 16, 3))
    return prepare_training(
        SimpleCNN(num_classes=4), dataset, optim.momentum(0.1, 0.9),
        batch_size=16, cycles=2, **kw)


def test_prepare_training_aot_compiles_then_loads(tmp_path):
    reg = get_registry()
    c0 = reg.value("fdtpu_aot_compiles_total")
    task = _prepare(aot=str(tmp_path))
    assert reg.value("fdtpu_aot_compiles_total") == c0 + 1
    files = [f for f in os.listdir(tmp_path) if f.startswith("train_step-")]
    assert len(files) == 1
    # the AOT step trains: run the loop end to end
    from fluxdistributed_tpu.train import train
    from fluxdistributed_tpu.train.logging import NullLogger

    params, _, task = train(task, print_every=0, eval_every=0,
                            logger=NullLogger())
    assert int(task.state.step) == 2
    # a second prepare with identical config LOADS the executable
    l0 = reg.value("fdtpu_aot_loads_total")
    task2 = _prepare(aot=str(tmp_path))
    assert reg.value("fdtpu_aot_loads_total") == l0 + 1
    state2, m = task2.step_fn(task2.state, task2.val_batch or _first_batch(task2))
    assert np.isfinite(float(m["loss"]))


def _first_batch(task):
    it = iter(task.loader)
    return next(it)


def test_prepare_training_warmup_leaves_state_pristine():
    """warmup=True pre-pays the step compile on donated zero dummies:
    the returned task's real state is bit-untouched (step counter still
    0) and the first train step reuses the warmed compile."""
    from fluxdistributed_tpu.obs import jaxmon

    task = _prepare(warmup=True)
    assert int(task.state.step) == 0
    c0 = jaxmon.compile_count()
    batch = _first_batch(task)
    state, m = task.step_fn(task.state, batch)
    assert int(state.step) == 1 and np.isfinite(float(m["loss"]))
    assert jaxmon.compile_count() == c0, "first real step recompiled"


def test_prepare_training_cache_dir_enables_cache(tmp_path,
                                                  restore_cache_config):
    task = _prepare(cache_dir=str(tmp_path / "cc"))
    resolved = compilation.persistent_cache_dir()
    assert resolved == str(tmp_path / "cc")
    assert jax.config.jax_compilation_cache_dir == resolved
    # the prepare-time compiles (model init) already populated it
    batch = _first_batch(task)
    task.step_fn(task.state, batch)
    assert os.listdir(resolved), "no cache entries written"


# ------------------------------------------------- cross-process cache reuse

_CHILD = r"""
import json, sys
import jax, jax.numpy as jnp
from fluxdistributed_tpu import compilation

resolved = compilation.enable_persistent_cache(sys.argv[1])
assert resolved, "cache must enable on this jax"

@jax.jit
def program(x, y):
    z = jnp.tanh(x @ y)
    return jnp.sum(z * z, axis=0)

x = jnp.ones((64, 64)); y = jnp.ones((64, 64))
jax.block_until_ready(program(x, y))
jax.block_until_ready(jax.jit(lambda a: jnp.cumsum(a, axis=1) / 7)(x))
print("METRICS " + json.dumps(compilation.compile_metrics()))
"""


@pytest.mark.slow
def test_second_process_zero_cache_misses(tmp_path):
    """THE acceptance demonstration: run 2 against run 1's persistent
    cache performs zero new XLA compiles — every compile request is a
    cache hit (``cache_misses == 0`` via the jaxmon counters; the raw
    compile-event counter fires on hits too on this jax, which is why
    misses are the honest signal)."""
    cache = str(tmp_path / "cc")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)  # plain 1-device CPU children
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    def run():
        p = subprocess.run(
            [sys.executable, "-c", _CHILD, cache],
            capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
        )
        assert p.returncode == 0, p.stderr[-3000:]
        line = [l for l in p.stdout.splitlines() if l.startswith("METRICS ")][-1]
        return json.loads(line[len("METRICS "):])

    first = run()
    assert first["cache_misses"] > 0, first   # cold: everything compiles
    assert first["cache_hits"] == 0, first
    second = run()
    assert second["cache_misses"] == 0, second  # warm: zero new compiles
    assert second["cache_hits"] == first["cache_misses"], second
    assert second["compile_seconds_saved"] >= 0.0
