"""DBSCAN — non-recursive, kernel-backed, preemption-safe.

Paper semantics (§II.C):
- non-recursive formulation ("it is not possible to use recursion with
  OpenCL") — here `lax.while_loop` replaces the paper's explicit work list;
- two accelerator kernels "that have almost the same purpose": core-point
  reachability in the main loop and cluster expansion — here
  :func:`repro.kernels.neighbor.epsilon_degree` and
  :func:`repro.kernels.neighbor.ops.expand_padded`;
- defaults: min_pts = 10 x features, eps = sqrt(features);
- per-point bookkeeping in one int16 word: "the first three bits indicate if
  the data item has been visited and the density reachability.  The other
  bits are used to store the cluster number (0 equals to noise).  The first
  three bits are deleted before the algorithm finishes."  Implemented
  verbatim in :func:`pack_state` / :func:`unpack_state` / :func:`finish`.

Cluster ids are assigned in discovery order with the lowest-index unvisited
core point as the next seed, so the partition — including contended border
points, which go to the earliest-discovered cluster — is deterministic and
bit-identical to the sequential oracle in tests.

TPU adaptation of the expansion: the GPU version expands one neighborhood
work-item at a time; here a whole frontier expands per kernel launch
(reach = A · frontier on the MXU), so the number of kernel launches per
cluster is its BFS depth, not its point count.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cancellation import CancellationToken
from repro.core.meter import StepMeter
from repro.kernels.neighbor.ops import (
    epsilon_degree,
    expand_padded,
    pad_points,
)
from repro.kernels.neighbor.ref import epsilon_degree_ref, expand_frontier_ref

# --- the paper's int16 state word ------------------------------------------

VISITED_BIT = 0x1     # bit 0: visited
REACHABLE_BIT = 0x2   # bit 1: density-reachable (member of some cluster)
CORE_BIT = 0x4        # bit 2: core point
FLAG_MASK = 0x7
CLUSTER_SHIFT = 3     # cluster id lives in bits 3..15; 0 = noise

# Largest cluster id the packed int16 word can carry: bit 15 is the sign
# bit, so ids occupy bits 3..14 — 4095 clusters.  Beyond that, `labels <<
# CLUSTER_SHIFT` wraps negative and silently corrupts every later unpack.
MAX_CLUSTER_ID = np.iinfo(np.int16).max >> CLUSTER_SHIFT


def pack_state(labels: jnp.ndarray, visited: jnp.ndarray,
               member: jnp.ndarray, core: jnp.ndarray) -> jnp.ndarray:
    """Pack per-point state into the paper's int16 word."""
    if not isinstance(labels, jax.core.Tracer):
        mx = int(jnp.max(labels)) if labels.size else 0
        if mx > MAX_CLUSTER_ID:
            raise ValueError(
                f"cluster id {mx} does not fit the paper's int16 state word "
                f"(bits {CLUSTER_SHIFT}..14 hold the cluster number, so at "
                f"most {MAX_CLUSTER_ID} clusters are representable); "
                f"shard the dataset or raise min_pts/eps"
            )
    word = (labels.astype(jnp.int32) << CLUSTER_SHIFT)
    word = word | jnp.where(visited, VISITED_BIT, 0)
    word = word | jnp.where(member, REACHABLE_BIT, 0)
    word = word | jnp.where(core, CORE_BIT, 0)
    return word.astype(jnp.int16)


def unpack_state(word: jnp.ndarray):
    w = word.astype(jnp.int32)
    labels = w >> CLUSTER_SHIFT
    return (
        labels,
        (w & VISITED_BIT) > 0,
        (w & REACHABLE_BIT) > 0,
        (w & CORE_BIT) > 0,
    )


def finish(word: jnp.ndarray) -> jnp.ndarray:
    """Paper: 'The first three bits are deleted before the algorithm
    finishes' — returns plain cluster ids (0 = noise)."""
    return ((word.astype(jnp.int32) & ~FLAG_MASK) >> CLUSTER_SHIFT).astype(
        jnp.int16
    )


# --- configuration -----------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DBSCANConfig:
    eps: float
    min_pts: int
    use_kernel: bool = True
    block_i: Optional[int] = None
    block_j: Optional[int] = None

    @staticmethod
    def paper_defaults(features: int) -> "DBSCANConfig":
        return DBSCANConfig(
            eps=float(np.sqrt(features)), min_pts=10 * features
        )


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("labels", "core_mask", "n_clusters", "expansions"),
    meta_fields=("cancelled",),
)
@dataclasses.dataclass
class DBSCANResult:
    labels: jax.Array       # (n,) int16, 0 = noise, clusters 1..C
    core_mask: jax.Array    # (n,) bool
    n_clusters: jax.Array   # () i32
    expansions: jax.Array   # () i32 — number of expansion-kernel launches
    cancelled: bool = False


def _degree(x, cfg: DBSCANConfig):
    if cfg.use_kernel:
        return epsilon_degree(x, cfg.eps, block_i=cfg.block_i,
                              block_j=cfg.block_j)
    return epsilon_degree_ref(x, cfg.eps)


def _points(x, cfg: DBSCANConfig):
    """The points as each expansion reads them: padded once for the
    kernel, as they are for the reference."""
    if cfg.use_kernel:
        return pad_points(x, block_i=cfg.block_i, block_j=cfg.block_j)
    return x


def _expand_step(xp, frontier, cfg: DBSCANConfig):
    """One expansion: the points within eps of the frontier, of the
    points as :func:`_points` gives them."""
    if cfg.use_kernel:
        return expand_padded(xp, frontier, cfg.eps, block_i=cfg.block_i,
                             block_j=cfg.block_j)
    return expand_frontier_ref(xp, frontier, cfg.eps)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _degree_step(x, cfg: DBSCANConfig):
    """Module-level jitted degree pass.  Jitted so the reference's
    (n, n, d) difference tensor fuses into its reduction instead of being
    materialised op by op."""
    return _degree(x, cfg)


# the breadth-first search's loop state: per-point labels, visited and
# member flags, the frontier of the cluster being expanded, that cluster's
# id and the expansions so far
Carry = Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array,
              jax.Array]


def _bfs(xp, core, carry: Carry, cfg: DBSCANConfig, expand,
         stop=None, max_cid=MAX_CLUSTER_ID) -> Carry:
    """The device loop of both solvers: expand the frontier while it
    holds points, else seed the next cluster at the lowest-index
    unvisited core point.  Ends when no core point is left unvisited,
    when seeding would pass ``max_cid``, or when the expansions reach
    ``stop`` (a traced count; ``None`` runs to the end)."""
    n = core.shape[0]

    def cond(c):
        _, visited, _, frontier, cid, nexp = c
        go = frontier.any() | ((core & ~visited).any() & (cid < max_cid))
        return go if stop is None else go & (nexp < stop)

    def grow(c):
        labels, visited, member, frontier, cid, nexp = c
        # unclaimed (noise or unvisited) points join this cluster; only
        # newly-claimed core points keep expanding
        new = expand(xp, frontier, cfg) & (labels == 0)
        return (jnp.where(new, cid, labels), visited | new, member | new,
                new & core, cid, nexp + 1)

    def seed(c):
        labels, visited, member, _, cid, nexp = c
        frontier = jnp.zeros((n,), bool).at[
            jnp.argmax(core & ~visited)].set(True)
        return labels, visited, member, frontier, cid + 1, nexp

    def body(c):
        _, _, _, frontier, _, _ = c
        return jax.lax.cond(frontier.any(), grow, seed, c)

    return jax.lax.while_loop(cond, body, carry)


@functools.partial(jax.jit, static_argnames=("cfg", "expand"))
def _expand_steps(x, core, carry: Carry, stop, cfg: DBSCANConfig,
                  expand=_expand_step):
    """One device program of the host-driven run: the search from
    ``carry`` until its expansions reach ``stop`` or the run ends.
    Returns the carry and whether the run is finished.  Module-level, so
    a service running many same-shaped requests compiles once per shape;
    ``stop`` is traced, so every stretch of a run shares that program."""
    carry = _bfs(_points(x, cfg), core, carry, cfg, expand, stop=stop)
    _, visited, _, frontier, _, _ = carry
    return carry, ~(frontier.any() | (core & ~visited).any())


def _start(n: int) -> Carry:
    return (jnp.zeros((n,), jnp.int32), jnp.zeros((n,), bool),
            jnp.zeros((n,), bool), jnp.zeros((n,), bool), jnp.int32(0),
            jnp.int32(0))


# --- fully jitted solver -----------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg",))
def fit(x: jnp.ndarray, cfg: DBSCANConfig) -> DBSCANResult:
    """Fully jitted DBSCAN: the whole search in one device loop."""
    core = _degree(x, cfg) >= cfg.min_pts
    # the answer's int16 labels bound the cluster ids, not the state word
    labels, _, _, _, cid, nexp = _bfs(
        _points(x, cfg), core, _start(x.shape[0]), cfg, _expand_step,
        max_cid=np.iinfo(np.int16).max)
    return DBSCANResult(
        labels=labels.astype(jnp.int16),
        core_mask=core,
        n_clusters=cid,
        expansions=nexp,
    )


# --- host-driven, cancellable + resumable solver ----------------------------


@dataclasses.dataclass
class DBSCANRunState:
    """Preemption snapshot of a host-driven run.

    ``packed`` is the paper's int16 word (labels + visited/member/core bits);
    ``frontier`` is the pending BFS frontier of the cluster being expanded
    when the run was interrupted (all-False at a cluster boundary).  Held as
    host numpy so it can be checkpointed without touching device state.
    """

    packed: np.ndarray    # (n,) int16
    frontier: np.ndarray  # (n,) bool
    cid: int
    nexp: int

    def as_tree(self) -> dict:
        """Checkpointable pytree (see repro.checkpoint.store)."""
        return {
            "packed": np.asarray(self.packed, np.int16),
            "frontier": np.asarray(self.frontier, bool),
            "cid": np.int32(self.cid),
            "nexp": np.int32(self.nexp),
        }

    @staticmethod
    def from_tree(tree: dict) -> "DBSCANRunState":
        return DBSCANRunState(
            packed=np.asarray(tree["packed"], np.int16),
            frontier=np.asarray(tree["frontier"], bool),
            cid=int(tree["cid"]),
            nexp=int(tree["nexp"]),
        )


def fit_resumable(
    x: jnp.ndarray,
    cfg: DBSCANConfig,
    token: Optional[CancellationToken] = None,
    *,
    state: Optional[DBSCANRunState] = None,
    valid_mask: Optional[jnp.ndarray] = None,
    on_progress: Optional[Callable[[int, int], None]] = None,
    on_state: Optional[Callable[[Callable[[], DBSCANRunState]], None]] = None,
    state_interval: int = 8,
    meter: Optional[StepMeter] = None,
) -> Tuple[DBSCANResult, Optional[DBSCANRunState]]:
    """Host loop over device programs; the abort flag is polled before
    each, as the paper polls it between kernel executions.  State is
    carried in the paper's packed int16 word.

    A program runs the search up to the next multiple of ``state_interval`` expansions, or to
    the end; one blocking read of (finished, cluster id, expansions)
    follows it, then ``on_progress(cid, nexp)`` and, where the run goes
    on, ``on_state``.  So cancellation is seen within ``state_interval``
    expansions.

    ``state`` resumes a previously interrupted run mid-BFS; on cancellation
    the returned second element is the snapshot to resume from (``None`` on
    normal completion).  ``on_state`` is invoked every ``state_interval``
    expansions of an unfinished run — the service's periodic-checkpoint
    hook — with a function that reads the snapshot back from the device
    when called (during the hook), so the caller's checkpoint covers the
    read-back that feeds it.  ``valid_mask`` marks real rows in a padded
    array: masked-out rows can never be core points (with min_pts=1 an
    isolated pad row would otherwise seed a phantom singleton cluster).
    ``meter`` counts the device programs (the degree pass and each
    stretch), the steps they ran (the degree pass and each expansion) and
    every blocking device-to-host read the loop makes.
    """
    meter = meter if meter is not None else StepMeter()
    n = x.shape[0]
    deg = _degree_step(x, cfg)       # kernel launch 1 (main loop kernel)
    meter.program()
    core = deg >= cfg.min_pts
    if valid_mask is not None:
        core = core & valid_mask

    if state is not None:
        labels, visited, member, _ = unpack_state(jnp.asarray(state.packed))
        cid = int(state.cid)
        nexp = int(state.nexp)
        carry = (labels, visited, member, jnp.asarray(state.frontier),
                 jnp.int32(cid), jnp.int32(nexp))
    else:
        carry = _start(n)
        cid = nexp = 0
    cancelled = False

    def _snapshot() -> DBSCANRunState:
        labels, visited, member, frontier, _, _ = carry
        return DBSCANRunState(
            packed=np.asarray(pack_state(labels, visited, member, core)),
            frontier=np.asarray(frontier),
            cid=cid,
            nexp=nexp,
        )

    def _read_snapshot() -> DBSCANRunState:
        # three reads: pack_state's cluster-id bound, the word, the frontier
        return meter.read(_snapshot, reads=3)

    while True:
        if token is not None and token.cancelled():
            cancelled = True
            break
        stop = (nexp // state_interval + 1) * state_interval
        # the expansion is looked up per call, so a replaced
        # `_expand_step` is traced afresh
        carry, done = _expand_steps(x, core, carry, jnp.int32(stop),
                                    cfg=cfg, expand=_expand_step)
        *_, cid_now, nexp_now = carry
        done, cid_now, nexp_now = meter.read(
            jax.device_get, (done, cid_now, nexp_now))
        meter.program(steps=int(nexp_now) - nexp)
        cid, nexp = int(cid_now), int(nexp_now)
        if on_progress is not None:
            on_progress(cid, nexp)
        if done:
            break
        if nexp < stop:
            # stopped short: the next cluster's id would not fit the word
            raise ValueError(
                f"dataset produced more than {MAX_CLUSTER_ID} clusters — the "
                f"paper's int16 state word cannot represent cluster id "
                f"{cid + 1}"
            )
        if on_state is not None:     # nexp == stop, a multiple of the interval
            on_state(_read_snapshot)

    labels, visited, member, _, _, _ = carry
    # pack_state reads the largest cluster id back to bound it
    packed = meter.read(pack_state, labels, visited, member, core)
    result = DBSCANResult(
        labels=finish(packed),
        core_mask=core,
        n_clusters=jnp.int32(cid),
        expansions=jnp.int32(nexp),
        cancelled=cancelled,
    )
    return result, (_read_snapshot() if cancelled else None)


def fit_cancellable(
    x: jnp.ndarray,
    cfg: DBSCANConfig,
    token: Optional[CancellationToken] = None,
    on_progress: Optional[Callable[[int, int], None]] = None,
) -> DBSCANResult:
    """Cancellable host loop (see :func:`fit_resumable` for the state API)."""
    result, _ = fit_resumable(x, cfg, token, on_progress=on_progress)
    return result


# --- sequential oracle (numpy BFS; used by tests and benchmarks) -------------


def fit_oracle(x: np.ndarray, cfg: DBSCANConfig) -> np.ndarray:
    """Textbook sequential DBSCAN with the same seed ordering.  O(n^2)."""
    x = np.asarray(x, np.float32)
    n = x.shape[0]
    d2 = ((x[:, None, :] - x[None, :, :]) ** 2).sum(-1)
    adj = d2 <= cfg.eps ** 2
    core = adj.sum(1) >= cfg.min_pts
    labels = np.zeros(n, np.int32)
    visited = np.zeros(n, bool)
    cid = 0
    for seed in range(n):
        if not core[seed] or visited[seed]:
            continue
        cid += 1
        frontier = np.zeros(n, bool)
        frontier[seed] = True
        while frontier.any():
            reached = (adj & frontier[None, :]).any(1)
            new = reached & (labels == 0)
            labels[new] = cid
            visited |= new
            frontier = new & core
    return labels
