"""Traffic kind ``dpps_jobs``: a closed loop of private-averaging jobs.

One client, through ``Session.run``: each job takes fresh private values
for every node (``values/<kind>.py`` of the configuration), runs ``rounds``
DPPS rounds in ``segment``-round compiled segments with its own noise key,
and reads the node-mean answer back to the host; the next job starts when
the last one returned. Mix parameters: ``rounds``, ``segment``,
``check_jobs`` (how many of the window's jobs, drawn from the seed, the
reference recomputes), ``trace_seconds`` and ``limits``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import compare, deploy, device, manifest, weights
from benchlib.harness import (CompileCounter, Outcome, free, hlo_text, span,
                              window)
from refs import dpps as ref_dpps


class Jobs:
    """Job j of a seed: its private values and its noise key. Every seed
    gives the same sizes; only the draws differ."""

    def __init__(self, cell, seed: int):
        self.k_vals, self.k_noise, self.k_sample = jax.random.split(
            weights.seed_key(seed), 3)
        cfg = cell.config
        maker = manifest.load_module(cell.bench_dir, "values",
                                     cfg["values"]["kind"])
        self._make = lambda key: maker.make(cfg, key, cfg["nodes"])

    def values(self, j: int):
        return self._make(jax.random.fold_in(self.k_vals, j))

    def noise_key(self, j: int):
        return jax.random.fold_in(self.k_noise, j)


def flat_rows(leaves) -> jax.Array:
    n = leaves[0].shape[0]
    return jnp.concatenate([x.reshape(n, -1) for x in leaves], axis=1)


def ref_job(cell, jobs: Jobs, j: int, *, precision: str = "highest",
            gossip: bool = True) -> tuple[np.ndarray, np.ndarray]:
    """The reference's (y (N, d_s), node-mean answer (d_s,)) of job j.
    ``precision="high"`` is the control, ``gossip=False`` a planted fault."""
    cfg, p = cell.config, cell.config["privacy"]
    w = (deploy.ref_weights(cell) if gossip
         else np.eye(cfg["nodes"], dtype=np.float32))
    final = ref_dpps.run(
        flat_rows(jobs.values(j)), jobs.noise_key(j), jnp.asarray(w),
        rounds=cell.traffic["rounds"], b=p["b"], gamma_n=p["gamma_n"],
        c_prime=p["c_prime"], lam=p["lam"],
        sync_interval=cfg["sync_interval"], precision=precision)
    y = ref_dpps.corrected(final)
    return np.asarray(y), np.asarray(jnp.mean(y, axis=0))


def drive(cell, seed: int, seconds: float, trace_dir: str | None,
          devs: list, t_start: float) -> Outcome:
    from repro.api import Session

    cfg, tr = cell.config, cell.traffic
    rounds = tr["rounds"]
    jobs = Jobs(cell, seed)
    session = Session.build(
        deploy.topology(cfg), privacy=deploy.privacy(cfg),
        schedule=cfg["schedule"], sync_interval=cfg["sync_interval"],
        use_kernels=cfg["use_kernels"], chunk=tr["segment"],
        packed=cfg["packed"], key=jobs.k_noise)

    def job(j: int):
        with span("job"):
            rep = session.run(rounds, values=jobs.values(j),
                              key=jobs.noise_key(j))
            answer = session.consensus(rep.state)
            with span("readback"):
                answer = [np.asarray(x) for x in answer]
        return rep.state.push, answer

    warm = 2**30   # a job index the window never reaches
    jax.block_until_ready(job(warm))
    setup_s = time.perf_counter() - t_start

    rng = np.random.default_rng(
        np.asarray(jax.random.key_data(jobs.k_sample)).tolist())
    keep = tr["check_jobs"]
    slots: list = []
    latencies = []
    failed = 0
    length = min(seconds, tr["trace_seconds"]) if trace_dir else seconds
    with CompileCounter() as compiles, window(trace_dir):
        w0 = time.perf_counter()
        j = 0
        while True:
            j0 = time.perf_counter()
            push, answer = job(j)
            latencies.append(time.perf_counter() - j0)
            failed += int(not all(np.all(np.isfinite(a)) for a in answer))
            # Reservoir sample of the window's jobs, drawn from the seed.
            if j < keep:
                slots.append((j, push, answer))
            else:
                r = int(rng.integers(0, j + 1))
                if r < keep:
                    slots[r] = (j, push, answer)
            del push, answer
            j += 1
            if time.perf_counter() - w0 >= length:
                break
        w_s = time.perf_counter() - w0
    dev = device.record(devs)
    hlo = ()
    if trace_dir:
        hlo = (hlo_text(session.consensus_runner(),
                        session.consensus_state(jobs.values(0)), None,
                        jobs.k_noise, rounds=min(tr["segment"], rounds)),)
    sampled = [(i, np.asarray(flat_rows(p.s) / p.a[:, None]),
                np.concatenate([np.asarray(a).reshape(-1) for a in ans]))
               for i, p, ans in sorted(slots, key=lambda s: s[0])]
    del slots, session
    free()

    gaps = []
    for i, y_prog, ans_prog in sampled:
        y_ref, ans_ref = ref_job(cell, jobs, i)
        gaps.append(max(compare.y_gap(y_prog, y_ref),
                        compare.y_gap(ans_prog, ans_ref)))
        failed += int(not gaps[-1] <= tr["limits"]["y_gap"])
    lat_ms = 1e3 * np.asarray(latencies)
    return Outcome(
        setup_s=setup_s,
        metrics={"consensus_rounds_per_s": len(latencies) * rounds / w_s,
                 "consensus_ms_p95": float(np.percentile(lat_ms, 95))},
        attempted=len(latencies), failed=failed,
        numbers={"y_gap": max(gaps), "window_compiles": float(compiles.count)},
        view={"rounds": len(latencies) * rounds},
        device=dev, hlo_texts=hlo)


def controls(cell, seed: int, jobs_per_seed: int = 2):
    """(variant, numbers) for the control and the planted faults of one
    seed, each read against the clean reference: the mix in three
    bfloat16 passes (the control), the exchange left out, the state left
    unchanged (every node answers its own value), one element of the
    answer altered by 1% of its largest value."""
    jobs = Jobs(cell, seed)
    for j in range(jobs_per_seed):
        y, ans = ref_job(cell, jobs, j)
        v = np.asarray(flat_rows(jobs.values(j)))
        y_c, ans_c = ref_job(cell, jobs, j, precision="high")
        y_g, ans_g = ref_job(cell, jobs, j, gossip=False)
        altered = ans.copy()
        altered[0] += 0.01 * np.max(np.abs(ans))
        for name, (yp, ap) in {
                "control": (y_c, ans_c), "no_exchange": (y_g, ans_g),
                "unchanged": (v, v.mean(axis=0)),
                "answer_altered": (y, altered)}.items():
            yield name, {"y_gap": max(compare.y_gap(yp, y),
                                      compare.y_gap(ap, ans))}
