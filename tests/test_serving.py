"""Serving engine + node runtime integration (real JAX execution, tiny models)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.runtime.accounting import MemoryAccountant
from repro.models import build_model
from repro.serving.engine import Engine, PromptTooLongError, Request
from repro.serving.node_runtime import NodeRuntime


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("qwen3-8b").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def test_engine_continuous_batching(tiny_model):
    cfg, m, params = tiny_model
    acc = MemoryAccountant(m_total=256e6)
    eng = Engine(m, params, acc, max_slots=3, s_max=64)
    rng = np.random.default_rng(0)
    reqs = [Request(req_id=i, tokens=list(rng.integers(0, cfg.vocab, 8)),
                    max_new=10) for i in range(8)]
    for r in reqs:
        eng.submit(r)
    done = eng.drain()
    assert len(done) == 8
    for r in done:
        assert len(r.out) >= 10
    assert acc.check_invariant()
    assert acc.m_kv == pytest.approx(0.0)     # everything reclaimed
    assert not eng.active and not eng.waiting


def test_engine_matches_unbatched_decode(tiny_model):
    """Greedy continuous-batched output == one-at-a-time decoding."""
    cfg, m, params = tiny_model
    prompt = list(range(1, 9))
    acc = MemoryAccountant(m_total=256e6)
    eng = Engine(m, params, acc, max_slots=2, s_max=64)
    eng.submit(Request(req_id=0, tokens=prompt, max_new=6))
    eng.submit(Request(req_id=1, tokens=prompt[::-1], max_new=6))
    done = {r.req_id: r.out for r in eng.drain()}

    acc2 = MemoryAccountant(m_total=256e6)
    for rid, toks in ((0, prompt), (1, prompt[::-1])):
        solo = Engine(m, params, acc2, max_slots=1, s_max=64)
        solo.submit(Request(req_id=99, tokens=list(toks), max_new=6))
        out = solo.drain()[0].out
        assert out == done[rid], (rid, out, done[rid])


def test_engine_backpressure(tiny_model):
    """With a tiny memory budget, admission rejects instead of OOMing."""
    cfg, m, params = tiny_model
    alpha = m.cfg.kv_bytes_per_token()
    acc = MemoryAccountant(m_total=alpha * 120.0)   # ~2 sequences worth
    eng = Engine(m, params, acc, max_slots=4, s_max=48)
    for i in range(6):
        eng.submit(Request(req_id=i, tokens=[1, 2, 3, 4], max_new=8))
    done = eng.drain()
    assert len(done) == 6           # eventually everyone runs
    assert acc.check_invariant()


def test_prompt_longer_than_window_rejected_typed(tiny_model):
    """Prompts that cannot fit s_max raise at submit() instead of silently
    overflowing the prefill write."""
    cfg, m, params = tiny_model
    eng = Engine(m, params, MemoryAccountant(m_total=256e6), max_slots=2,
                 s_max=16)
    with pytest.raises(PromptTooLongError):
        eng.submit(Request(req_id=0, tokens=list(range(16)), max_new=4))
    eng.submit(Request(req_id=1, tokens=list(range(15)), max_new=4))
    assert len(eng.drain()) == 1                 # boundary prompt still runs


def test_release_observes_the_admitted_reservation(tiny_model):
    """rho.observe must be fed the R_need admission charged, not a value
    recomputed after earlier releases already moved the shared estimator."""
    cfg, m, params = tiny_model
    eng = Engine(m, params, MemoryAccountant(m_total=256e6), max_slots=1,
                 s_max=64)
    needs, observed = [], []
    orig_need, orig_obs = eng.rho.r_need, eng.rho.observe
    eng.rho.r_need = lambda x: needs.append(orig_need(x)) or needs[-1]
    eng.rho.observe = \
        lambda a, r: observed.append(r) or orig_obs(a, r)
    rng = np.random.default_rng(1)
    for i in range(10):      # pred_len << actual so rho moves mid-stream
        eng.submit(Request(req_id=i, tokens=list(rng.integers(0, 64, 6)),
                           max_new=8, pred_len=1.0))
    eng.drain()
    assert len(needs) == 10                      # r_need at admission ONLY
    assert eng.rho.rho > eng.rho.lo              # estimator really moved
    for got, want in zip(observed, needs):
        assert got == pytest.approx(want)


def test_sleep_frees_engine_kv_and_recovers_headroom():
    """Regression for the sleep leak: offloading a model must free its arena
    pages AND its dense state cache, and the accountant must reflect it."""
    zoo, host = {}, {}
    for name in ("qwen3-8b", "mamba2-2.7b"):
        c = get_config(name).reduced()
        mm = build_model(c)
        zoo[name] = mm
        host[name] = jax.tree.map(np.asarray, mm.init(jax.random.PRNGKey(2)))
    node = NodeRuntime(0, 0, zoo, host, hbm_budget=1e9, max_slots=2, s_max=48)
    node.activate("mamba2-2.7b")
    node.submit("mamba2-2.7b", Request(req_id=0, tokens=[3, 4, 5], max_new=4))
    node.step()                                  # admitted + decoding
    eng = node.engines["mamba2-2.7b"]
    assert eng._state_bytes > 0                  # SSM state is accounted
    assert eng.pool.n_pages > 0
    h_active = node.acc.headroom
    node.sleep("mamba2-2.7b")
    recovered = node.acc.headroom - h_active
    weights = node.profiles["mamba2-2.7b"].weight_bytes
    assert recovered >= weights                  # weights AND KV came back
    assert eng._state_bytes == 0 and eng.cache is None
    assert node.arena.mapped_pages() == 0
    assert eng.waiting                           # in-flight work requeued
    # self-heal: step() reactivates and the requeued request completes
    out = {}
    for _ in range(30):
        for mdl, reqs in node.step().items():
            out.setdefault(mdl, []).extend(reqs)
    assert len(out.get("mamba2-2.7b", [])) == 1
    assert len(out["mamba2-2.7b"][0].out) >= 4


def test_node_runtime_colocation_and_warm_reactivation():
    zoo, host = {}, {}
    for name in ("qwen3-8b", "starcoder2-15b"):
        c = get_config(name).reduced()
        mm = build_model(c)
        zoo[name] = mm
        host[name] = jax.tree.map(np.asarray, mm.init(jax.random.PRNGKey(1)))
    node = NodeRuntime(0, 0, zoo, host, hbm_budget=1e9, max_slots=2, s_max=48)
    t_cold = node.activate("qwen3-8b")
    node.submit("qwen3-8b", Request(req_id=0, tokens=[5, 6, 7], max_new=4))
    for _ in range(8):
        node.step()
    node.sleep("qwen3-8b")
    assert "qwen3-8b" not in node.device_params
    t_warm = node.activate("qwen3-8b")
    assert t_warm < t_cold            # executable cache survived (Fig. 10)
    sig = node.signal()
    assert sig.headroom > 0
    assert "qwen3-8b" in sig.warm_models


@pytest.mark.parametrize("chunk", [0, 8])
def test_request_logits_are_the_rows_tokens_were_picked_from(tiny_model,
                                                             chunk):
    """A request that asks for logits gets one f32 row per host-picked
    token (prefill or chunk, then each one-token decode), and each row's
    argmax is the token; a request that does not ask keeps none."""
    cfg, m, params = tiny_model
    eng = Engine(m, params, MemoryAccountant(m_total=256e6), max_slots=2,
                 s_max=64, prefill_chunk_tokens=chunk)
    rng = np.random.default_rng(1)
    asked = Request(req_id=0, tokens=list(rng.integers(0, cfg.vocab, 20)),
                    max_new=5, logits=[])
    plain = Request(req_id=1, tokens=list(rng.integers(0, cfg.vocab, 9)),
                    max_new=5)
    eng.submit(asked)
    eng.submit(plain)
    eng.drain()
    assert plain.logits is None
    assert len(asked.logits) == len(asked.out) == 5
    rows = np.stack([np.asarray(r) for r in asked.logits])
    assert rows.dtype == np.float32 and rows.shape[1] == m.vocab_padded
    assert list(rows.argmax(axis=-1)) == asked.out


def test_offload_releases_the_engines_weights():
    """A slept model's weights are no longer referenced by its engine (they
    would stay on the device), and reactivation gives them back."""
    cfg = get_config("qwen3-8b").reduced()
    mm = build_model(cfg)
    host = {"qwen3-8b": jax.tree.map(np.asarray,
                                     mm.init(jax.random.PRNGKey(1)))}
    node = NodeRuntime(0, 0, {"qwen3-8b": mm}, host, hbm_budget=1e9,
                       max_slots=2, s_max=48)
    node.activate("qwen3-8b")
    eng = node.engines["qwen3-8b"]
    assert eng.params is node.device_params["qwen3-8b"]
    node.sleep("qwen3-8b")
    assert eng.params is None
    node.activate("qwen3-8b")
    assert eng.params is node.device_params["qwen3-8b"]


def test_host_params_are_numpy_drawn_on_the_cpu():
    from repro.serving.cluster import host_params
    m = build_model(get_config("qwen3-8b").reduced())
    host = host_params(m, 3)
    ref = m.init(jax.random.PRNGKey(3))
    for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(ref)):
        assert isinstance(a, np.ndarray)
        np.testing.assert_array_equal(a, np.asarray(b))


def test_fleet_nodes_each_hold_their_state_on_their_own_device():
    """On a host with two devices, node i sits on device i: its weights,
    arena planes and step outputs live there (CPU devices stand in for
    chips, in a child process so the device count can be set)."""
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent("""
        import jax
        from repro.serving.cluster import ClusterSpec, NodeSpec, build_fleet
        from repro.serving.engine import Request
        spec = ClusterSpec(nodes=(NodeSpec(0, max_slots=2, s_max=48),
                                  NodeSpec(1, max_slots=2, s_max=48)),
                           model_names=("qwen3-8b",))
        fleet = build_fleet(spec)
        devs = jax.local_devices()
        assert len(devs) == 2
        for i, node in enumerate(fleet):
            assert node.device == devs[i]
            node.submit("qwen3-8b", Request(req_id=i, tokens=[3, 4, 5],
                                            max_new=3))
            out = {}
            for _ in range(6):
                out.update(node.step())
            assert len(out["qwen3-8b"][0].out) == 3
            leaves = jax.tree.leaves(node.device_params["qwen3-8b"])
            assert {d for x in leaves for d in x.devices()} == {devs[i]}
            for plane in node.arena.planes.values():
                assert plane.k.devices() == plane.v.devices() == {devs[i]}
        print("OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src"),
                    os.environ.get("PYTHONPATH", "")]))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0 and r.stdout.strip().endswith("OK"), r.stderr
