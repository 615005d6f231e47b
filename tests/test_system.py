"""End-to-end behaviour tests: the full PartPSP trainer on a reduced
assigned architecture, optimizer substrate, and launcher plumbing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.partpsp import privacy_summary
from repro.data import NodeShardedLoader, SyntheticLMStream
from repro.launch import train as train_cli
from repro.launch.train import build_session, build_trainer
from repro.optim import adamw, global_norm, sgd


def _train(arch="llama3.2-1b", algorithm="partpsp", steps=6, **kw):
    # gamma_n within the sensitivity-feedback stability region for the
    # smoke-scale shared sets (see EXPERIMENTS.md SClaims)
    defaults = dict(reduced=True, n_nodes=4, b=3.0, gamma_n=1e-6,
                    gamma_l=0.05, gamma_s=0.05, clip=100.0, topology="dout",
                    degree=2, sync_interval=4, schedule="dense", seed=0)
    defaults.update(kw)
    model, model_cfg, topo, cfg, partition, state, step = build_trainer(
        arch, algorithm=algorithm, **defaults)
    stream = SyntheticLMStream(vocab_size=model_cfg.vocab_size, seq_len=16,
                               n_nodes=defaults["n_nodes"], seed=0)
    loader = NodeShardedLoader(stream, per_node_batch=2, seed=0)
    hist = []
    for t in range(steps):
        batch = loader.batch_at(t)
        state, m = step(state, batch, jax.random.fold_in(jax.random.PRNGKey(1), t))
        hist.append({k: float(v) for k, v in m.items()
                     if jnp.ndim(v) == 0})
    return cfg, partition, state, hist


def test_end_to_end_partpsp_on_reduced_llama():
    cfg, partition, state, hist = _train()
    assert all(np.isfinite(h["loss_mean"]) for h in hist)
    assert all(h["sensitivity_used"] > 0 for h in hist)
    assert partition.d_shared() > 0 and partition.d_local() > 0
    s = privacy_summary(cfg, len(hist))
    assert s["epsilon_total"] == pytest.approx(len(hist) * 3.0 / 1e-6)


def test_end_to_end_sgp_loss_decreases():
    cfg, _, _, hist = _train(algorithm="sgp", steps=30, gamma_l=0.1,
                             gamma_s=0.1)
    first = np.mean([h["loss_mean"] for h in hist[:5]])
    last = np.mean([h["loss_mean"] for h in hist[-5:]])
    assert last < first


def test_end_to_end_circulant_schedule():
    cfg, _, _, hist = _train(schedule="circulant", steps=4)
    assert all(np.isfinite(h["loss_mean"]) for h in hist)


def test_end_to_end_kernel_path():
    cfg, _, _, hist = _train(use_kernels=True, steps=3)
    assert all(np.isfinite(h["loss_mean"]) for h in hist)


def test_end_to_end_xlstm():
    cfg, _, _, hist = _train(arch="xlstm-125m", steps=3)
    assert all(np.isfinite(h["loss_mean"]) for h in hist)


def test_end_to_end_moe():
    cfg, _, _, hist = _train(arch="llama4-scout-17b-a16e", steps=3)
    assert all(np.isfinite(h["loss_mean"]) for h in hist)


def test_end_to_end_zamba():
    cfg, _, _, hist = _train(arch="zamba2-7b", steps=3)
    assert all(np.isfinite(h["loss_mean"]) for h in hist)


@pytest.mark.parametrize("use_kernels", [None, True, False])
def test_build_session_routes_kernels_by_platform(use_kernels):
    """The trainer pins nothing: None leaves the choice to the plan (on iff
    the backend is TPU), an explicit value wins."""
    _, _, session = build_session(
        "xlstm-125m", reduced=True, n_nodes=2, algorithm="partpsp", b=3.0,
        gamma_n=1e-6, gamma_l=0.05, gamma_s=0.05, clip=100.0,
        topology="dout", use_kernels=use_kernels)
    want = (jax.default_backend() == "tpu" if use_kernels is None
            else use_kernels)
    assert session.plan.use_kernels is want


def test_train_cli_returns_its_report(monkeypatch, tmp_path):
    # a placed cache dir: the entry point must not set one of its own
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    report = train_cli.main(["--arch", "xlstm-125m", "--reduced",
                             "--nodes", "2", "--steps", "2", "--chunk", "2",
                             "--gamma-n", "1e-6", "--log-every", "10",
                             "--no-use-kernels"])
    losses = np.asarray(report.trajectory["loss_mean"])
    assert report.rounds == 2 and losses.shape == (2,)
    assert np.isfinite(losses).all()


# ---------------------------------------------------------------------------
# optimizer substrate
# ---------------------------------------------------------------------------

def _quad_problem():
    key = jax.random.PRNGKey(0)
    target = jax.random.normal(key, (12,))
    params = {"w": jnp.zeros((12,))}

    def grads(p):
        return {"w": 2 * (p["w"] - target)}

    return params, grads, target


def test_sgd_momentum_converges():
    params, grads, target = _quad_problem()
    opt = sgd(0.1, momentum=0.5)
    state = opt.init(params)
    for _ in range(100):
        params, state = opt.update(grads(params), state, params)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=1e-3)


def test_adamw_converges():
    params, grads, target = _quad_problem()
    opt = adamw(0.1)
    state = opt.init(params)
    for _ in range(300):
        params, state = opt.update(grads(params), state, params)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=1e-2)


def test_global_norm():
    assert float(global_norm({"a": jnp.ones(4), "b": jnp.ones(4) * 2})) == \
        pytest.approx(np.sqrt(4 + 16))
