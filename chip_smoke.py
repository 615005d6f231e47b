#!/usr/bin/env python3
"""Chip smoke test: the main path, once, on TPU, through the normal entry points.

    python chip_smoke.py                # one chip: DPPS consensus + PartPSP training
    python chip_smoke.py --four-chips   # four chips: the sharded engine only

One process holds the chip and runs every phase; it starts no child process.

* **device** — refuses to run unless JAX's first device is a TPU.
* **dpps** — DPPS consensus at the paper-MLP shared width (N=16 nodes,
  d_s=7850, 2-out graph, packed buffer, dense schedule) through
  ``Session.build(...).run(...)`` with the plan's default kernel routing.
  The compiled segment must hold Pallas kernels (``tpu_custom_call``).
  Noiseless, it must agree with the same session at ``use_kernels=False``
  to f32 tolerance; noised, every node must reach consensus within
  ``CONSENSUS_TOL``.
* **train** — PartPSP through ``repro.launch.train`` at the full xlstm-125m
  width on 4 nodes, for a few steps in two scan segments. Losses must be
  finite, and the first step's loss must match the ``--no-use-kernels`` run.
* **four-chips** (``--four-chips``, and nothing else) — ``shard_run_dpps`` on
  a (4, 1) ("data", "model") mesh at N=16, d_s=7850, circulant
  (collective-permute) and dense (all-gather), against the single-device
  engine in the noiseless regime; each chip must hold its own shard.

Compile seconds (or, where the compile is not separated, the first
segment's seconds: compile plus its run) and steady-state seconds are
printed for information. The last line
of standard output is one JSON object: ``{"ok": true, "device": {...}}``.
Any failed check exits non-zero without printing it.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

SEED = 2024
N_NODES = 16
LEAF_SHAPES = ((784, 10), (10,))   # paper MLP shared layer: d_s = 7850
# The 2-out graph (self loop + next node) mixes slowly: ~0.98 contraction per
# round, so consensus to CONSENSUS_TOL takes a few hundred rounds, and the
# sensitivity recursion stays bounded only for a small noise step gamma_n.
DPPS_ROUNDS, DPPS_CHUNK, DPPS_GAMMA_N = 500, 100, 1e-6
# BENCH_async's rounds-to-tolerance threshold on max |y_i - mean_j y_j|,
# at the same N=16, d_s=7850 payload
CONSENSUS_TOL = 1e-3
F32_ATOL = 1e-5
# The Laplace scale is S/b with the sensitivity S ~ 2 C' ||s||_1, which grows
# with d_s: at xlstm-125m's 95.7M shared parameters gamma_n = 1e-6 adds noise
# of order 1 per coordinate and the loss diverges by step 2. gamma_n = 1e-9
# keeps the noise near 1e-3 per coordinate, under the weights' own scale.
TRAIN_STEPS, TRAIN_CHUNK = 4, 2
LOSS_RTOL = 1e-4


def train_args(steps: int) -> list[str]:
    return ["--arch", "xlstm-125m", "--nodes", "4", "--steps", str(steps),
            "--chunk", str(TRAIN_CHUNK), "--gamma-n", "1e-9",
            "--log-every", "1"]


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def device_record() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# -- DPPS consensus ------------------------------------------------------------

def _consensus_error(state) -> float:
    """max_i |y_i - mean_j y_j| over every shared coordinate (NaN if any
    coordinate is not finite)."""
    a = np.asarray(state.push.a)
    y = np.concatenate([np.asarray(leaf).reshape(a.shape[0], -1)
                        for leaf in jax.tree_util.tree_leaves(state.push.s)],
                       axis=1) / a[:, None]
    return float(np.abs(y - y.mean(axis=0)).max())


def _values() -> list[jax.Array]:
    key = jax.random.PRNGKey(SEED)
    return [jax.random.normal(jax.random.fold_in(key, i),
                              (N_NODES,) + shape, jnp.float32)
            for i, shape in enumerate(LEAF_SHAPES)]


def dpps_phase() -> None:
    from repro.api import PrivacySpec, Session, make_topology

    topo = make_topology("dout", N_NODES, degree=2)
    values = _values()

    def session(noise: bool, use_kernels: bool | None):
        return Session.build(
            topo, privacy=PrivacySpec(b=3.0, gamma_n=DPPS_GAMMA_N,
                                      noise=noise),
            schedule="dense", sync_interval=0, use_kernels=use_kernels,
            chunk=DPPS_CHUNK, packed=True, seed=SEED)

    finals = {}
    for noise in (False, True):
        ses = session(noise, None)
        check(ses.plan.use_kernels, "the plan did not route kernels on TPU")
        t0 = time.time()
        hlo = ses.consensus_runner().lower(
            ses.consensus_state(values), None, ses.base_key,
            rounds=DPPS_CHUNK).compile().as_text()
        compile_s = time.time() - t0
        n_kernels = hlo.count("tpu_custom_call")
        check(n_kernels > 0, f"no tpu_custom_call in the compiled segment "
                             f"(noise={noise})")
        rep = ses.run(DPPS_ROUNDS, values=values)
        steady = rep.run_s / (DPPS_ROUNDS - DPPS_CHUNK)
        err = _consensus_error(rep.state)
        finals[noise] = rep.state
        log(f"dpps noise={noise}: kernels={n_kernels} compile_s={compile_s:.3f}"
            f" first_segment_s={rep.compile_s:.4f}"
            f" steady_s_per_round={steady:.7f} consensus_err={err:.3e}")
        check(bool(np.isfinite(err)), f"non-finite state (noise={noise})")
        if noise:
            check(err < CONSENSUS_TOL,
                  f"noised consensus error {err:.3e} >= {CONSENSUS_TOL}")

    ref = session(False, False)
    check(not ref.plan.use_kernels, "use_kernels=False was not honoured")
    rep = ref.run(DPPS_ROUNDS, values=values)
    diff = max(float(np.abs(np.asarray(x) - np.asarray(y)).max())
               for x, y in zip(jax.tree_util.tree_leaves(finals[False].push.s),
                               jax.tree_util.tree_leaves(rep.state.push.s)))
    diff_a = float(np.abs(np.asarray(finals[False].push.a)
                          - np.asarray(rep.state.push.a)).max())
    log(f"dpps noiseless kernels vs jnp: max|ds|={diff:.3e} "
        f"max|da|={diff_a:.3e} (jnp first_segment_s={rep.compile_s:.3f})")
    check(diff <= F32_ATOL and diff_a <= F32_ATOL,
          f"kernel and jnp paths disagree: {diff:.3e} / {diff_a:.3e}")


# -- PartPSP training ----------------------------------------------------------

def _train(args: list[str]) -> tuple[np.ndarray, float, float]:
    """One ``repro.launch.train`` run -> (losses, first segment s, rest s).

    Only these leave the function: the final training state is dropped
    before the next run, which needs the chip's memory for its own."""
    from repro.launch import train

    rep = train.main(args)
    return np.asarray(rep.trajectory["loss_mean"]), rep.compile_s, rep.run_s


def _peak_gb() -> float:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", float("nan")) / 2**30


def train_phase() -> None:
    losses, first_s, rest_s = _train(train_args(TRAIN_STEPS))
    log(f"train xlstm-125m kernels: first_segment_s={first_s:.3f} "
        f"steady_s_per_step={rest_s / (TRAIN_STEPS - TRAIN_CHUNK):.4f} "
        f"peak_hbm_gib={_peak_gb():.2f} losses={losses.tolist()}")
    check(losses.shape == (TRAIN_STEPS,),
          f"expected {TRAIN_STEPS} losses, got {losses}")
    check(bool(np.isfinite(losses).all()), f"non-finite loss: {losses}")

    gc.collect()  # the first run's state must be gone before the second
    ref_losses, ref_first_s, _ = _train(train_args(TRAIN_CHUNK)
                                        + ["--no-use-kernels"])
    log(f"train xlstm-125m jnp: first_segment_s={ref_first_s:.3f} "
        f"losses={ref_losses.tolist()}")
    check(bool(np.isclose(losses[0], ref_losses[0], rtol=LOSS_RTOL)),
          f"first-step loss {losses[0]} != jnp path {ref_losses[0]}")


# -- four chips: the sharded engine --------------------------------------------

def four_chip_phase() -> None:
    from jax.sharding import Mesh

    from repro.api import make_topology
    from repro.core.dpps import DPPSConfig, dpps_init
    from repro.core.topology import calibrate_constants
    from repro.engine import ProtocolPlan, run_dpps, shard_run_dpps

    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chips needs 4 devices, found {len(devs)}")
    mesh = Mesh(np.asarray(devs[:4]).reshape(4, 1), ("data", "model"))
    s0 = _values()
    rounds = 40
    eps_seq = [jnp.zeros((rounds,) + x.shape, x.dtype) for x in s0]
    key = jax.random.PRNGKey(SEED)
    for schedule, marker in (("circulant", "collective-permute"),
                             ("dense", "all-gather")):
        topo = make_topology("dout", N_NODES, degree=2)
        cp, lam = calibrate_constants(topo)
        cfg = DPPSConfig(noise=False, gamma_n=0.0, c_prime=cp, lam=lam,
                         sync_interval=3, schedule=schedule)
        plan = ProtocolPlan.from_topology(topo, mesh=mesh, schedule=schedule,
                                          sync_interval=3)
        cfg_r = plan.resolve_dpps(cfg)
        ref, _ = jax.jit(functools.partial(run_dpps, cfg=cfg, plan=plan))(
            dpps_init(s0, cfg_r), eps_seq, key)
        sharded = jax.jit(functools.partial(shard_run_dpps, mesh, cfg=cfg,
                                            plan=plan))
        t0 = time.time()
        compiled = sharded.lower(dpps_init(s0, cfg_r), eps_seq, key).compile()
        compile_s = time.time() - t0
        check(marker in compiled.as_text(), f"{schedule}: no {marker} in HLO")
        sh, _ = sharded(dpps_init(s0, cfg_r), eps_seq, key)
        jax.block_until_ready(sh)
        t0 = time.time()
        sh, _ = sharded(dpps_init(s0, cfg_r), eps_seq, key)
        jax.block_until_ready(sh)
        run_s = time.time() - t0
        diff = max(float(np.abs(np.asarray(x) - np.asarray(y)).max())
                   for x, y in zip(jax.tree_util.tree_leaves(ref.push.s),
                                   jax.tree_util.tree_leaves(sh.push.s)))
        leaf = jax.tree_util.tree_leaves(sh.push.s)[0]
        holders = sorted({s.device.id for s in leaf.addressable_shards})
        rows = sorted({s.data.shape[0] for s in leaf.addressable_shards})
        log(f"four-chips {schedule}: compile_s={compile_s:.3f} "
            f"s_per_round={run_s / rounds:.6f} max|ds|={diff:.3e} "
            f"shard_devices={holders} rows_per_shard={rows}")
        check(diff <= F32_ATOL, f"{schedule}: sharded != single-device "
                                f"({diff:.3e})")
        check(holders == sorted(d.id for d in devs[:4]) and rows == [4],
              f"{schedule}: node axis not split over the 4 chips "
              f"({holders}, rows {rows})")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-engine phase on four chips")
    args = ap.parse_args(argv)

    dev = device_record()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is {dev['platform']});"
              " refusing to run", file=sys.stderr)
        return 2
    log(f"device: kind={dev['kind']} count={dev['count']} "
        f"jax={jax.__version__}")

    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    phases = ([("four-chips", four_chip_phase)] if args.four_chips
              else [("dpps", dpps_phase), ("train", train_phase)])
    for name, phase in phases:
        t0 = time.time()
        phase()
        log(f"phase {name}: ok in {time.time() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
