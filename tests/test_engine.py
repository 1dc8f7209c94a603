"""Engine runtime tests: compiled-step cache, noise-key threading, and the
continuous-batching serve loop (slot surgery vs sequential decode, steady-state
recompile freedom, chaos-drill recovery, straggler hook)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.configs.base import ShapeConfig
from repro.core.fabric import FabricSpec, NoiseSpec
from repro.launch.compat import ambient_mesh, mesh_context
from repro.launch.engine import Engine
from repro.launch.mesh import make_test_mesh
from repro.launch.server import Request, Server
from repro.models.model import decode_step, init_params, prefill
from repro.optim.adamw import AdamWConfig, init_adamw
from repro.runtime.straggler import StragglerMonitor

MAX_NEW = 6
PROMPT = 16


@pytest.fixture(scope="module")
def cfg():
    return reduce_config(get_config("qwen2.5-3b"))


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(jax.random.key(0), cfg)


def _requests(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rng.integers(0, cfg.vocab_size,
                                 size=PROMPT).astype(np.int32),
                    max_new_tokens=MAX_NEW)
            for _ in range(n)]


def _uniform_server(cfg, params, eng, **kw):
    """A two-slot Server whose one bucket holds the uniform ``PROMPT``."""
    return Server(cfg, params, engine=eng, slots=2, buckets=(PROMPT,), **kw)


# ----------------------------------------------------------- compat shim
def test_mesh_context_installs_ambient_mesh():
    assert ambient_mesh() is None
    mesh = make_test_mesh()
    with mesh_context(mesh):
        amb = ambient_mesh()
        assert amb is not None
        assert tuple(amb.axis_names) == ("data", "model")
    assert ambient_mesh() is None


# ---------------------------------------------------- compiled-step cache
def test_compiled_step_cache_returns_same_executable(cfg):
    eng = Engine()
    d1 = eng.decode_step(cfg)
    d2 = eng.decode_step(cfg)
    assert d1 is d2
    assert eng.stats.compiles == 1 and eng.stats.hits == 1

    # equal-but-distinct ModelConfig values hit the same entry
    cfg_copy = dataclasses.replace(cfg)
    assert cfg_copy is not cfg
    assert eng.decode_step(cfg_copy) is d1
    assert eng.stats.compiles == 1 and eng.stats.hits == 2

    # a different FabricSpec is a different executable
    other = dataclasses.replace(cfg, fabric=FabricSpec(mode="exact"),
                                imc_mode="off")
    assert eng.decode_step(other) is not d1
    assert eng.stats.compiles == 2

    # kinds and prefill extras are distinct cache entries, stable per key
    p1 = eng.prefill_step(cfg, max_new_tokens=4)
    assert eng.prefill_step(cfg, max_new_tokens=4) is p1
    assert eng.prefill_step(cfg, max_new_tokens=8) is not p1
    t1 = eng.train_step(cfg, AdamWConfig(lr=1e-3))
    assert eng.train_step(cfg, AdamWConfig(lr=1e-3)) is t1
    assert eng.train_step(cfg, AdamWConfig(lr=2e-3)) is not t1


def test_aot_compile_cell(cfg):
    eng = Engine()
    shape = ShapeConfig("tiny_train", 32, 2, "train")
    aot = eng.aot_compile(cfg, shape)
    assert aot.compiled.memory_analysis() is not None
    shape_d = ShapeConfig("tiny_decode", 32, 2, "decode")
    aot_d = eng.aot_compile(cfg, shape_d)
    assert aot_d.compiled is not None


# --------------------------------------------- continuous-batching serve
def _sequential_decode(cfg, params, req):
    """Unbatched (B=1) greedy reference for one request."""
    logits, cache = prefill(params, {"tokens": jnp.asarray(req.prompt[None])},
                            cfg, max_new_tokens=MAX_NEW)
    out = [int(jnp.argmax(logits[0]))]
    while len(out) < MAX_NEW:
        logits, cache = decode_step(
            params, cache, jnp.asarray([[out[-1]]], jnp.int32), cfg)
        out.append(int(jnp.argmax(logits[0])))
    return out


def test_batched_serve_matches_sequential_decode(cfg, params):
    reqs = _requests(cfg, 5)
    eng = Engine()
    with eng.activate():
        server = _uniform_server(cfg, params, eng)
        handles = [server.submit(r) for r in reqs]
        server.drain()
    for h in handles:
        assert h.tokens == _sequential_decode(cfg, params, h.request), \
            f"req{h.rid}: batched stream diverged from sequential decode"


def test_serve_steady_state_no_recompiles(cfg, params):
    eng = Engine()
    with eng.activate():
        server = _uniform_server(cfg, params, eng)
        server.submit(_requests(cfg, 1)[0])
        server.drain()  # warm every executable (prefill/admit/decode)
        warm = eng.stats.traces
        for r in _requests(cfg, 4, seed=1):
            server.submit(r)
        handles = server.drain()
    assert all(len(h.tokens) == MAX_NEW for h in handles)
    assert eng.stats.traces == warm, \
        "admit/retire slot surgery must not retrace the compiled steps"
    assert eng.stats.compiles == warm, \
        "steady state reuses the warm-up executables (no new compiles)"


def test_serve_fault_injection_recovers_identical_streams(cfg, params):
    eng = Engine()
    with eng.activate():
        server = _uniform_server(cfg, params, eng)
        for r in _requests(cfg, 3):
            server.submit(r)
        baseline = server.drain()
        crashed = _uniform_server(cfg, params, eng, fail_at=(1,))
        for r in _requests(cfg, 3):
            crashed.submit(r)
        recovered = crashed.drain()
    assert crashed.recoveries == 1
    for b, r in zip(baseline, recovered):
        assert b.tokens == r.tokens, \
            f"req{b.rid}: stream changed across injected failure"


def test_straggler_hook_flags_slow_host():
    mon = StragglerMonitor()
    eng = Engine(monitor=mon)
    for _ in range(mon.cfg.patience + 3):
        eng.observe_step_time(0.1, host=0)
        eng.observe_step_time(0.1, host=1)
        eng.observe_step_time(1.0, host=2)  # 10x the median
    assert eng.swap_requests == [2]


# -------------------------------------------------- noisy key threading
def _noisy_cfg(cfg):
    spec = FabricSpec(bits_a=2, bits_w=2, mode="sim", backend="jnp",
                      noise=NoiseSpec(mismatch_sigma=0.3))
    return dataclasses.replace(cfg, fabric=spec, imc_mode="off")


@pytest.mark.parametrize("step,slot", [(0, 0), (1, 0), (3, 5), (4097, 7)])
def test_noise_key_is_the_two_fold_ins(step, slot):
    """One compiled call gives the bits of fold_in(fold_in(base, step), slot)."""
    got = Engine(noise_seed=42).noise_key(step, slot)
    want = jax.random.fold_in(jax.random.fold_in(jax.random.key(42), step),
                              slot)
    np.testing.assert_array_equal(jax.random.key_data(got),
                                  jax.random.key_data(want))


def test_noisy_serve_keys_thread_through_jit(cfg, params):
    ncfg = _noisy_cfg(cfg)
    prompt = np.arange(PROMPT, dtype=np.int32)[None] % ncfg.vocab_size

    def tokens(seed):
        eng = Engine(noise_seed=seed)
        with eng.activate():
            pf = eng.prefill_step(ncfg, max_new_tokens=3)
            dec = eng.decode_step(ncfg)
            logits, cache = pf(params, {"tokens": prompt}, eng.noise_key(0))
            out = [int(np.argmax(logits[0]))]
            for t in range(1, 4):
                logits, cache = dec(params, cache,
                                    np.asarray([[out[-1]]], np.int32),
                                    eng.noise_key(t))
                out.append(int(np.argmax(logits[0])))
        return out

    assert tokens(0) == tokens(0), "same seed must give identical tokens"
    assert tokens(0) != tokens(7), \
        "different seeds must draw different noise (keys are traced, not baked)"


@pytest.mark.slow
def test_noisy_train_keys_thread_through_jit(cfg):
    ncfg = dataclasses.replace(_noisy_cfg(cfg), remat=False)
    params0 = init_params(jax.random.key(0), ncfg)
    batch = {"tokens": np.zeros((2, 8), np.int32),
             "labels": np.ones((2, 8), np.int32)}

    eng = Engine()
    with eng.activate():
        step = eng.train_step(ncfg, donate=False)

        def losses(seed):
            e = Engine(noise_seed=seed)
            out = []
            p, o = params0, init_adamw(params0)
            for s in range(2):
                p, o, m = step(p, o, batch, e.noise_key(s))
                out.append(float(m["loss"]))
            return out

        a, b, c = losses(0), losses(0), losses(7)
    assert a == b, "same seed must be bit-identical across runs"
    assert a != c, "different seeds must differ"
    assert eng.stats.compiles == 1, "both runs share one executable"
