"""Batched-JAX experiment backend: the whole grid as device lanes.

Adapter between the declarative experiment layer and the batched
device-resident engine (:mod:`repro.sweep.batch`): cells become fixed-shape
lanes grouped by static pass structure — greedy-structured strategies
(EASY/MIN/PREF/KEEPPREF/rigid_sjf) share one engine batch and one
compilation, while AVG (balanced), pref_common_pool (pooled) and
steal_agreement (stealing) each add one more batch only when present —
and lanes of *different* workloads pad-stack into the same batch
(:func:`repro.sweep.batch.concat_lanes`) so a single compilation serves all
four supercomputer grids.  Per-cell metrics come back through
:mod:`repro.sweep.metrics_jax`; only lanes that ran to completion are
written to the cell store.

Execution is chunked and shardable (:mod:`repro.sweep.shard`): the
``chunk_lanes`` budget streams each structure's batch as sequential lane
chunks sized for the box, and ``devices`` lane-shards every chunk across a
1-D local device mesh.  Each completed chunk's cells are **flushed to the
store before the next chunk starts**, so an interrupted paper-scale run
resumes chunk-by-chunk (see ``docs/paper-scale.md``).  Both knobs are
results-neutral by construction — chunked/sharded cells are bit-identical
to the monolithic batch (``tests/test_shard.py``) — and therefore never
part of a spec or cell fingerprint.

Scenario axes: walltime accuracy/distribution, arrival compression and
job classes are applied to the trace before lane construction
(bit-identical to the DES backend's input); ``backfill_depth`` is lane
data that bounds the engine's EASY scan itself
(:mod:`repro.core.passes`), so every scenario axis is engine-faithful —
the spec's depth both keys the cell store *and* changes the schedule.

The lane executor (:func:`lane_batches`, :class:`LaneBatch`) is the one
way from cells to device lanes to cell metrics: :func:`run_cells` stacks
one :class:`LaneGroup` per workload, the what-if service
(:mod:`repro.serve.whatif`) one per query.

Backend options (results-neutral tuning, not part of the spec) are read
in one place, :func:`engine_configs`; a key left out takes the
:class:`EngineConfig` / :class:`ShardConfig` default: ``window``
(active-set ladder floor, 0 = statics-predicted start), ``chunk`` (scan
steps between compactions), ``chunk_lanes`` (max device-resident lanes,
0 = whole batch), ``devices`` (lane shards, 0 = all local devices),
``events`` (per-lane events retired per scan step, event compression),
``aot_warmup`` (background ladder pre-compilation), ``max_steps_factor``
(step budget), ``expand_backend`` (``bisect`` | ``pallas`` |
``pallas-interpret`` | ``fused`` | ``fused-interpret``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Iterator, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from repro import obs
from repro.core import (DONE, Cluster, TransformConfig, Window, Workload,
                        get_strategy)
from repro.sweep.batch import (EngineConfig, build_lanes, concat_lanes,
                               simulate_lanes)  # noqa: F401 (re-export)
from repro.sweep.cache import SweepCache
from repro.sweep.metrics_jax import batched_metrics
from repro.sweep.shard import (ChunkResult, ShardConfig, describe_plan,
                               simulate_lanes_chunked)

from .spec import Cell, ExperimentSpec, prepare_workload

# the backend options that set EngineConfig / ShardConfig fields
ENGINE_OPTIONS = ("window", "chunk", "max_steps_factor", "expand_backend",
                  "events", "aot_warmup")
SHARD_OPTIONS = ("chunk_lanes", "devices")


def engine_configs(options: Optional[Dict], structure: str
                   ) -> Tuple[EngineConfig, ShardConfig]:
    """The engine and execution-plan configs ``options`` ask for; a key
    left out takes the dataclass default."""
    opts = options or {}
    cfg = EngineConfig(structure=structure,
                       **{k: opts[k] for k in ENGINE_OPTIONS if k in opts})
    shard = ShardConfig(**{k: opts[k] for k in SHARD_OPTIONS if k in opts})
    return cfg, shard


class LaneGroup(NamedTuple):
    """One realised workload and the cells to run on it, one lane each.

    ``lanes`` pairs each cell with the caller's key for it; the key comes
    back with the cell's metrics.
    """

    cluster: Cluster
    workload: Workload
    window: Window
    transform: TransformConfig
    backfill_depth: int
    queue_order: str
    lanes: Sequence[Tuple[object, Cell]]


class LaneChunk(NamedTuple):
    """One executed chunk: the keys, metric dicts (``sched_*`` counters
    attached) and ran-to-completion flags of its lanes."""

    chunk: ChunkResult
    keys: Sequence[object]
    metrics: List[Dict[str, float]]
    done: np.ndarray


@dataclasses.dataclass
class LaneBatch:
    """The lanes of one static pass structure: one engine batch, its
    groups stacked in order (each holding only this structure's lanes)."""

    structure: str
    groups: List[LaneGroup]
    config: EngineConfig
    shard: ShardConfig

    @property
    def keys(self) -> List[object]:
        return [key for g in self.groups for key, _ in g.lanes]

    def chunks(self, verbose: bool = False) -> Iterator[LaneChunk]:
        """Build the lanes, stream them through the chunked plan and yield
        each chunk's cell metrics.  The consumer's handling of a chunk
        runs inside its ``experiment.metrics`` span."""
        keys = self.keys
        with obs.span("experiment.lanes", structure=self.structure,
                      lanes=len(keys)):
            batches, t0s, t1s, caps = [], [], [], []
            for g in self.groups:
                lanes = [(get_strategy(s), p, sd) for _, (s, p, sd) in g.lanes]
                batch, _order = build_lanes(
                    g.workload, g.cluster.nodes, lanes, config=g.transform,
                    tick=g.cluster.tick, backfill_depth=g.backfill_depth,
                    queue_order=g.queue_order)
                batches.append(batch)
                t0s += [g.window.t0] * len(lanes)
                t1s += [g.window.t1] * len(lanes)
                caps += [g.cluster.nodes] * len(lanes)
            big = concat_lanes(batches) if len(batches) > 1 else batches[0]
        win0, win1 = np.asarray(t0s), np.asarray(t1s)
        caps_arr = np.asarray(caps)
        for ch in simulate_lanes_chunked(big, self.config, self.shard,
                                         verbose=verbose):
            with obs.span("experiment.metrics", structure=self.structure,
                          lanes=ch.hi - ch.lo):
                res = ch.results
                per_lane = batched_metrics(
                    res, big.submit[ch.lo:ch.hi], big.malleable[ch.lo:ch.hi],
                    (win0[ch.lo:ch.hi], win1[ch.lo:ch.hi]),
                    caps_arr[ch.lo:ch.hi])
                # device-accumulated per-lane scheduling counters ride in the
                # metric dicts (execution-plan-invariant; never fingerprinted)
                shrink_ev = np.sum(res["shrink_ops"], axis=1)
                expand_ev = np.sum(res["expand_ops"], axis=1)
                for i, m in enumerate(per_lane):
                    m["sched_backfill_starts"] = float(res["bf_starts"][i])
                    m["sched_shrink_events"] = float(shrink_ev[i])
                    m["sched_expand_events"] = float(expand_ev[i])
                    m["sched_invocations"] = float(res["sched_steps"][i])
                yield LaneChunk(ch, keys[ch.lo:ch.hi], per_lane,
                                np.all(res["state"] == DONE, axis=1))


def lane_batches(groups: Sequence[LaneGroup],
                 options: Optional[Dict] = None) -> List[LaneBatch]:
    """Split the groups' lanes into one batch per static pass structure
    (greedy / balanced / pooled / stealing), in order of first appearance;
    non-malleable lanes (easy, rigid_sjf) are pure data and ride the
    greedy batch with everything else greedy-shaped."""
    by_structure: Dict[str, List[LaneGroup]] = {}
    for g in groups:
        split: Dict[str, list] = {}
        for key, cell in g.lanes:
            split.setdefault(get_strategy(cell[0]).structure,
                             []).append((key, cell))
        for structure, lanes in split.items():
            by_structure.setdefault(structure, []).append(
                g._replace(lanes=lanes))
    return [LaneBatch(structure, members, *engine_configs(options, structure))
            for structure, members in by_structure.items()]


def run_cells(spec: ExperimentSpec,
              todo: List[Tuple[str, Cell]],
              store: Optional[SweepCache],
              fingerprints: Dict[Tuple[str, Cell], Dict],
              options: Optional[Dict] = None,
              verbose: bool = True) -> Tuple[Dict, Dict]:
    """Run ``todo`` cells on the batched engine; one batch per structure.

    Each structure's batch is executed through the chunked/sharded plan
    (:func:`repro.sweep.shard.simulate_lanes_chunked`); with the default
    plan that is one monolithic chunk, i.e. exactly the historical
    behaviour.  Completed cells are written to the store per chunk, and
    ``info["chunks"]`` records each chunk's wall-clock — split into
    compile vs. execute by first-call timing, plus retrace and
    window-escalation counts — and executed lane width (surfaced into
    ``artifacts/sweep-timing-jax.json`` by ``benchmarks/run.py``).

    Every cell's metric dict carries the device-accumulated ``sched_*``
    scheduling counters (backfill starts, shrink/expand events, processed
    scheduling ticks).  They are execution-plan-invariant — derived from
    the bit-identical schedule, so chunked/sharded/monolithic runs agree
    exactly — and execution-only: stored with the cell, never part of a
    fingerprint.  ``options["progress"]`` prints a per-chunk heartbeat
    line (chunks done, cells flushed, ETA).
    """
    opts = options or {}
    names = [n for n in spec.workloads if any(n == m for m, _ in todo)]
    # one lane group per workload; lanes of different workloads pad-stack
    # into each structure's batch
    groups = []
    for name in names:
        cl, w_rigid, window = prepare_workload(spec, name)
        groups.append(LaneGroup(
            cl, w_rigid, window, spec.transform,
            spec.scenario.backfill_depth, spec.scenario.queue_order,
            [(key, key[1]) for key in todo if key[0] == name]))
    batches = lane_batches(groups, opts)
    label = "+".join(names)
    t0 = time.monotonic()
    metrics: Dict[Tuple[str, Cell], Dict[str, float]] = {}
    info: Dict[str, object] = {"incomplete": [], "chunks": [],
                               "chunk_lanes": batches[0].shard.chunk_lanes,
                               "peak_lane_width": 0,
                               "compile_s": 0.0, "execute_s": 0.0,
                               "compile_variants": 0,
                               "retraces": 0, "aot_rejits": 0,
                               "escalations": 0,
                               "warm_hits": 0, "compressed_events": 0,
                               "sched_steps": 0}
    for batch in batches:
        tag = batch.structure
        n_lanes = len(batch.keys)
        plan = describe_plan(n_lanes, batch.shard)
        if verbose:
            if plan["chunks"] > 1 or plan["devices"] > 1:
                print(f"[experiment-jax:{label}] {tag} plan: "
                      f"{plan['n_lanes']} lanes as {plan['chunks']} "
                      f"chunk(s) of width {plan['lane_width']} on "
                      f"{plan['devices']} device(s)")
        heartbeat = obs.Heartbeat(
            plan["chunks"], label=f"progress:{label}:{tag}",
            unit="chunk", enabled=bool(opts.get("progress")))
        steps_total, window_peak, budget_cut = 0, 0, False
        variants_peak = 0  # chunks of one structure share compile keys
        for out in batch.chunks(verbose=verbose):
            ch, res = out.chunk, out.chunk.results
            # only completed lanes enter the persistent store: a lane cut
            # off by the step budget has partial metrics that must not be
            # replayed.  The flush happens before the next chunk runs, so
            # an interrupted stream resumes from the last finished chunk.
            flushed = 0
            for key, m, done in zip(out.keys, out.metrics, out.done):
                metrics[key] = m
                if bool(done):
                    if store is not None:
                        store.put(fingerprints[key], m)
                        flushed += 1
                else:
                    info["incomplete"].append(key)
            steps_total += int(res["steps"])
            window_peak = max(window_peak, int(res["window"]))
            budget_cut = budget_cut or not res["finished"]
            info["chunks"].append({
                "structure": tag, "lanes": ch.hi - ch.lo,
                "lane_width": ch.lane_width, "devices": ch.n_devices,
                "wall_s": ch.wall_s, "steps": int(res["steps"]),
                "window": int(res["window"]),
                "compile_s": float(res["compile_s"]),
                "execute_s": float(res["execute_s"]),
                "compile_variants": int(res.get("compile_variants", 0)),
                "retraces": int(res["retraces"]),
                "aot_rejits": int(res["aot_rejits"]),
                "escalations": int(res["escalations"]),
                "warm_hits": int(res["warm_hits"]),
                "sched_steps": int(np.sum(res["sched_steps"])),
                "compressed_events": int(res["compressed_events"]),
            })
            info["compile_s"] += float(res["compile_s"])
            info["execute_s"] += float(res["execute_s"])
            variants_peak = max(variants_peak,
                                int(res.get("compile_variants", 0)))
            info["retraces"] += int(res["retraces"])
            info["aot_rejits"] += int(res["aot_rejits"])
            info["escalations"] += int(res["escalations"])
            info["warm_hits"] += int(res["warm_hits"])
            info["sched_steps"] += int(np.sum(res["sched_steps"]))
            info["compressed_events"] += int(res["compressed_events"])
            info["peak_lane_width"] = max(info["peak_lane_width"],
                                          ch.lane_width)
            info["devices"] = ch.n_devices
            heartbeat.tick(cells_flushed=flushed)
        info[f"{tag}_lanes"] = n_lanes
        info[f"{tag}_steps"] = steps_total
        info[f"{tag}_window"] = window_peak
        # distinct chunk-kernel configs across the run: chunks within one
        # structure batch share keys (max), structures add batches (sum)
        info["compile_variants"] += variants_peak
        if budget_cut:
            print(f"[experiment-jax:{label}] WARNING: {tag} batch "
                  "hit the step budget with unfinished lanes")
    info["sim_seconds"] = time.monotonic() - t0
    # lanes cut off by the step budget are *attempted*, not computed:
    # counting them as computed would make --expect-cached resume
    # summaries overstate coverage (they were never written to the store)
    info["computed_cells"] = len(todo) - len(info["incomplete"])
    return metrics, info
