"""A run with the timed path broken underneath comes out not correct.

Each test drives a whole run of a cell on the CPU at a small size, past
the harness's look for a chip, with one fault planted in the program, and
reads ``correct`` from its result.  The sound run of the same size comes
out correct.  On the CPU the device lanes run the XLA path (``jax-ref``);
the Pallas lane's kernels would run in interpret mode, too slowly for a
test.
"""

import os

import numpy as np
import pytest

import run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cell(algo):
    config = run.load_json(BENCH, "configs", "paper-grid.json")
    config["grid"] = {"features": [2], "clusters": [4],
                      "points_per_cluster": [128, 256]}
    if algo == "kmeans":
        traffic = {"loop": "open", "algo": "kmeans", "rate": 8.0,
                   "check": {"sample": 6, "largest": 2}}
    else:
        traffic = {"loop": "closed", "algo": "dbscan", "clients": 2,
                   "max_rate": 400.0, "check": {"sample": 4, "largest": 1}}
    cell = {"name": f"test-{algo}", "chips": 1}
    return cell, config, traffic


def xla_lanes_only(monkeypatch):
    """Device lanes for every request, and the XLA one of the two."""
    from repro.service import dispatch, exec_cache

    orig = dispatch.ParadigmRegistry.candidates

    def candidates(self, *a, **kw):
        names = orig(self, *a, **kw)
        keep = [n for n in names if n == dispatch.EXECUTOR_JAX_REF]
        return keep or [dispatch.EXECUTOR_JAX_REF]

    monkeypatch.setattr(dispatch.ParadigmRegistry, "candidates", candidates)
    monkeypatch.setattr(exec_cache, "_default", None)


def _step_unchanged(monkeypatch):
    import jax
    import jax.numpy as jnp

    from repro.core import kmeans

    def step(x, c, mask, cfg):
        assign, c_new, shift, inertia = kmeans.masked_kmeans_step(
            x, c, mask, cfg)
        return assign, c, jnp.float32(0.0), inertia

    fn = jax.jit(step, static_argnames=("cfg",))
    monkeypatch.setattr(kmeans, "masked_step_fn", lambda cfg: fn)


def _half_batch(monkeypatch):
    import jax
    import jax.numpy as jnp

    from repro.core import kmeans

    def step(x, c, mask, cfg):
        assign, _, _, _ = kmeans.masked_kmeans_step(x, c, mask, cfg)
        half = mask & (jnp.arange(x.shape[0]) < jnp.sum(mask) // 2)
        _, c_new, shift, inertia = kmeans.masked_kmeans_step(
            x, c, half, cfg)
        return assign, c_new, shift, inertia * 2

    fn = jax.jit(step, static_argnames=("cfg",))
    monkeypatch.setattr(kmeans, "masked_step_fn", lambda cfg: fn)


def _expand_unchanged(monkeypatch):
    from repro.core import dbscan

    monkeypatch.setattr(dbscan, "_expand_step",
                        lambda x, frontier, cfg: frontier)


def _answer_altered(monkeypatch):
    from repro.service import dispatch

    orig = dispatch.JaxParadigm.execute

    def execute(self, plan, items, token, on_item_done, *a, **kw):
        def done(i, labels, scalars):
            labels = np.asarray(labels).copy()
            labels[labels == 1] = 2 if plan.algo == "kmeans" else 0
            on_item_done(i, labels, scalars)

        return orig(self, plan, items, token, done, *a, **kw)

    monkeypatch.setattr(dispatch.JaxParadigm, "execute", execute)


FAULTS = {
    "kmeans": {"none": None, "step_returns_state_unchanged": _step_unchanged,
               "half_batch_left_out": _half_batch,
               "answer_altered": _answer_altered},
    "dbscan": {"none": None,
               "step_returns_state_unchanged": _expand_unchanged,
               "answer_altered": _answer_altered},
}


@pytest.mark.parametrize("algo,fault", [(a, f) for a in FAULTS
                                        for f in FAULTS[a]])
def test_fault_makes_run_not_correct(algo, fault, monkeypatch):
    xla_lanes_only(monkeypatch)
    if FAULTS[algo][fault] is not None:
        FAULTS[algo][fault](monkeypatch)
    cell, config, traffic = _cell(algo)
    result = run.run_cell(cell, config, traffic, 2**32 + 17, 2.0, False, [],
                          require_accelerator=False,
                          out=open(os.devnull, "w"))
    assert result["attempted"] > 0
    assert result["correct"] is (fault == "none"), result["checks"]
