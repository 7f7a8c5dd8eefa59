"""Serving-strategy search: the paper's search loop, turned loose on the
decode tick.

The repo's thesis (PAPER.md) is that an MCMC search over a simulator
beats hand-rolled parallelism choices — but until now every serving knob
(`page_size`, `prefill_chunk`, spec tree width/depth, pool size, mesh
layout) was hand-picked. This module
closes that gap:

  1. a `ServeStrategy` (flexflow_tpu/serve_strategy.py, the serving
     side's own leaf; re-exported here) names one point in the serving
     knob space and knows how to configure `serve_generation`
     (`to_server_kwargs`);
  2. `ServePricer` prices one strategy's *decode tick* against a named
     traffic profile (search/traffic.py): ragged launch shapes and
     padding waste per the PR 10 packing, chunked-prefill TTFT, the
     spec tree's expected accepted tokens/step
     (SpecConfig.expected_tokens_per_step), the host's cost a dispatch
     (cost_model.TickPricer), page size vs pool occupancy,
     and the KV pool's HBM bill (cost_model.kv_cache_token_bytes) —
     with the per-token compute rate coming from the SAME step pricing
     the sharding search uses (eventsim.step_seconds), per candidate
     mesh layout;
  3. the EXISTING drivers search the space: mcmc.anneal_assignment over
     a knob-valued StrategyTable, table.coordinate_descent as the
     polish, and mcmc_optimize itself pricing each candidate mesh
     layout's step — one search machinery, train and serve;
  4. `fftrace calibrate` reports feed `MeasuredCostModel.
     set_tick_calibration`, so measured per-tick-shape wall times scale
     the analytic prices (reports older than the staleness window are
     REFUSED).

Surface: `serve_generation(search_budget=...)` /
`FFModel.serve_generation(...)` run the search at serve time;
`tools/servesearch.py` (search / explain / apply) emits the winning
strategy as JSON the server loads back. docs/search.md "Serving
strategy search" is the narrative.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import math
import os
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from flexflow_tpu.search.cost_model import (
    HOST_DISPATCH_SECONDS,
    TickPricer,
    graph_cost,
    kv_cache_elem_counts,
    kv_cache_token_bytes,
)
from flexflow_tpu.search.table import StrategyTable, coordinate_descent
from flexflow_tpu.serve_strategy import PREFILL_WINDOW_ROWS, ServeStrategy

logger = logging.getLogger(__name__)

# A calibration report older than this is refused (with a warning), not
# silently used.
CALIBRATION_MAX_AGE_S = 7 * 24 * 3600

# Objective assigned to knob combinations serve_generation would reject
# (half-set speculation, oversized pages, ...): finite so the anneal's accept
# rule stays well-defined, large enough that no walk settles there.
INVALID_OBJECTIVE = 1e9

# The spec-acceptance prior used when neither the caller nor the traffic
# profile supplies one (a RecordedProfile's measured acceptance wins —
# see search_serve_strategy's acceptance_rate resolution).
DEFAULT_ACCEPTANCE_RATE = 0.6


# ---------------------------------------------------------------------------
# Objective


@dataclasses.dataclass(frozen=True)
class ServeObjective:
    """Composable SLO objective, minimized: ttft_weight * TTFT p95 +
    throughput_weight * seconds-per-decoded-token, plus the mcmc memory
    penalty (1e3 * hbm/budget) when the strategy's resident bytes exceed
    hbm_budget_bytes — tokens/sec AT a fixed HBM budget, not traded
    against it."""

    ttft_weight: float = 1.0
    throughput_weight: float = 1.0
    hbm_budget_bytes: Optional[float] = None

    def breakdown(self, m: Dict) -> Dict[str, float]:
        terms = {
            "ttft_term": self.ttft_weight * m["ttft_p95_s"],
            "throughput_term":
                self.throughput_weight / max(m["tokens_per_s"], 1e-9),
            "hbm_penalty": 0.0,
        }
        if self.hbm_budget_bytes and m["hbm_bytes"] > self.hbm_budget_bytes:
            terms["hbm_penalty"] = 1e3 * (m["hbm_bytes"]
                                          / self.hbm_budget_bytes)
        return terms

    def value(self, m: Dict) -> float:
        return sum(self.breakdown(m).values())

    def to_json(self) -> Dict:
        return {"ttft_weight": self.ttft_weight,
                "throughput_weight": self.throughput_weight,
                "hbm_budget_bytes": self.hbm_budget_bytes}

    @classmethod
    def from_json(cls, d: Dict) -> "ServeObjective":
        return cls(**d)


# ---------------------------------------------------------------------------
# Calibration hand-off (fftrace calibrate -> MeasuredCostModel)


def load_calibration(report, max_age_s: Optional[float] = None,
                     now: Optional[float] = None) -> Optional[Dict]:
    """Load + freshness-check an `fftrace calibrate` report (path or
    dict). Returns the report, or None — with a logged warning — when it
    predates the schema-v2 created-at stamp or is older than
    `max_age_s` (default CALIBRATION_MAX_AGE_S, overridable via
    FLEXFLOW_CALIBRATION_MAX_AGE): stale scale factors silently applied
    are worse than none."""
    if isinstance(report, (str, os.PathLike)):
        with open(report) as f:
            report = json.load(f)
    if max_age_s is None:
        max_age_s = float(os.environ.get("FLEXFLOW_CALIBRATION_MAX_AGE",
                                         CALIBRATION_MAX_AGE_S))
    created = report.get("created_at_unix")
    if created is None:
        logger.warning(
            "calibration report has no created_at_unix stamp (schema v%s "
            "predates it) — refusing it; re-run `fftrace calibrate` to get "
            "a stamped v2 report", report.get("version", "?"))
        return None
    age = (time.time() if now is None else now) - float(created)
    if age > max_age_s:
        logger.warning(
            "calibration report is %.1f days old (stamp %s, max %.1f "
            "days) — refusing stale scale factors; re-run `fftrace "
            "calibrate` against a fresh serving run",
            age / 86400.0, report.get("created_at", created),
            max_age_s / 86400.0)
        return None
    return report


# ---------------------------------------------------------------------------
# Layout pricing: one priced step per candidate serving mesh, found by
# the EXISTING sharding search (mcmc_optimize + greedy_polish)


@dataclasses.dataclass
class PricedLayout:
    """One candidate serving-mesh layout, priced: the best sharding
    strategy the existing search found for it, the eventsim/graph_cost
    step seconds that sharding prices at, its per-chip weight/activation
    bytes, and the per-token K/V bytes its head sharding leaves on each
    chip."""

    axis_sizes: Dict[str, int]
    strategy: Dict
    step_s: float
    base_tokens: int
    mem_bytes: float
    kv_token_bytes: int
    mode: str
    # dtype-independent counts (cost_model.kv_cache_elem_counts) so the
    # pricer can re-bill the pool per candidate kv_dtype without
    # re-walking the graph: K/V elements per token row, and scale-
    # sidecar entries per PAGE when the dtype is quantized
    kv_token_elems: int = 0
    kv_scale_elems: int = 0

    @property
    def mesh_key(self) -> Tuple[Tuple[str, int], ...]:
        return tuple(sorted(self.axis_sizes.items()))

    def summary(self) -> Dict:
        return {"mesh": dict(self.axis_sizes), "step_s": self.step_s,
                "mem_bytes": self.mem_bytes,
                "kv_token_bytes": self.kv_token_bytes,
                "pricing_mode": self.mode}


def price_layouts(graph, cost, layouts: Sequence[Dict[str, int]], *,
                  inner_budget: int = 0, seed: int = 0
                  ) -> List[PricedLayout]:
    """Price each candidate mesh layout's forward step. With
    inner_budget > 0 each layout's sharding comes from the EXISTING
    mcmc_optimize (anneal + DP polish) under that layout's axis sizes —
    the serving search literally nests the training search; at 0 the
    compiled shardings (or the DP default for a foreign layout) price
    it."""
    from flexflow_tpu.search import space as space_mod
    from flexflow_tpu.search.eventsim import step_seconds
    from flexflow_tpu.search.mcmc import mcmc_optimize
    from flexflow_tpu.obs.calibrate import graph_tokens

    priced = []
    for axis_sizes in layouts:
        cm = dataclasses.replace(cost, axis_sizes=dict(axis_sizes))
        if inner_budget > 0:
            strategy = mcmc_optimize(
                graph, cm, budget=inner_budget, seed=seed, training=False,
                memory_limit=cm.machine.memory_per_chip())
        elif dict(axis_sizes) == dict(cost.axis_sizes):
            strategy = {n.name: n.sharding for n in graph.nodes
                        if n.sharding is not None}
        else:
            strategy = space_mod.default_dp_strategy(graph, cm.axis_sizes)
        step_s, mode = step_seconds(graph, strategy, cm, training=False)
        gc = graph_cost(graph, strategy, cm, training=False)
        elems, scale_elems = kv_cache_elem_counts(graph, strategy,
                                                  cm.axis_sizes)
        priced.append(PricedLayout(
            axis_sizes=dict(axis_sizes), strategy=strategy,
            step_s=step_s, base_tokens=graph_tokens(graph),
            mem_bytes=gc.memory_per_chip,
            kv_token_bytes=kv_cache_token_bytes(graph, strategy,
                                                cm.axis_sizes),
            mode=mode, kv_token_elems=elems, kv_scale_elems=scale_elems))
    return priced


# ---------------------------------------------------------------------------
# The pricer: ServeStrategy x traffic profile -> tick-level metrics


class ServePricer:
    """Closed-form serving model of one strategy under one traffic
    profile. Everything is expectations over the profile's analytic
    moments (traffic.prompt_stats) — no sampling, so one evaluation is
    microseconds and the anneal can afford thousands."""

    def __init__(self, layouts: Sequence[PricedLayout],
                 stats: Dict[str, float], *, slots: int, max_len: int,
                 acceptance_rate: float = DEFAULT_ACCEPTANCE_RATE,
                 host_dispatch_s: float = HOST_DISPATCH_SECONDS,
                 tick_scale: Optional[Callable] = None):
        self.layouts = list(layouts)
        self.by_mesh = {lay.mesh_key: lay for lay in self.layouts}
        self.stats = dict(stats)
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.acceptance_rate = float(acceptance_rate)
        self.host_dispatch_s = float(host_dispatch_s)
        self.tick_scale = tick_scale

    def _layout(self, mesh: Tuple[Tuple[str, int], ...]) -> PricedLayout:
        if not mesh:
            return self.layouts[0]
        try:
            return self.by_mesh[tuple(mesh)]
        except KeyError:
            raise ValueError(
                f"strategy mesh {mesh} is not among the priced layouts "
                f"{sorted(self.by_mesh)}") from None

    @staticmethod
    def _bucket(n: float) -> int:
        """A pow2 launch bucket (floor 8)."""
        n = max(int(math.ceil(n)), 1)
        return max(8, 1 << (n - 1).bit_length())

    def metrics(self, s: ServeStrategy) -> Dict[str, float]:
        lay = self._layout(s.mesh)
        pricer = TickPricer(base_step_s=lay.step_s,
                            base_tokens=lay.base_tokens,
                            host_dispatch_s=self.host_dispatch_s,
                            tick_scale=self.tick_scale)
        st = self.stats
        slots, max_len = self.slots, self.max_len
        page = min(s.page_size, max_len)
        chunk = min(s.prefill_chunk, max_len)
        mean_p = st["mean_prompt_tokens"]
        p95_p = st["p95_prompt_tokens"]
        share = st["prefix_share_rate"]
        new_t = max(st["new_tokens"], 1.0)
        offered = max(st["offered_concurrency"], 1.0)

        # -- pool occupancy: page size vs tokens in flight --------------
        pages_per_seq = -(-max_len // page)
        if s.pool_fraction >= 1.0:
            pages = slots * pages_per_seq + 1
        else:
            pages = max(int(math.ceil(
                s.pool_fraction * slots * pages_per_seq)) + 1,
                pages_per_seq + 1)
        pool_tokens = pages * page
        # resident tokens one live request uniquely holds: the uncached
        # prompt suffix (the shared prefix's pages are refcounted once),
        # half its decode budget on average, and half a page of internal
        # fragmentation — the page-size tax
        resident = (1.0 - share) * mean_p + new_t / 2.0 + page / 2.0
        live = max(1.0, min(offered, slots, pool_tokens / resident))
        occupancy = min(1.0, live * resident / pool_tokens)

        # -- decode launch shape: packed rows vs padding waste ----------
        launch_rows = self._bucket(live)
        padded = max(launch_rows - live, 0.0)

        # -- chunked prefill padding (both dispatch models below) -------
        uncached_mean = (1.0 - share) * mean_p
        uncached_p95 = (1.0 - share) * p95_p
        w = min(PREFILL_WINDOW_ROWS, chunk)
        pad_pre = -(-chunk // w) * w - chunk

        # -- decode dispatch: one token a row, or spec verify -----------
        spec = s.spec_config()
        if spec is not None:
            accepted = spec.expected_tokens_per_step(self.acceptance_rate)
            t_disp = pricer.verify_dispatch(live, spec.max_nodes,
                                            padded_rows=padded)
        else:
            accepted = 1.0
            t_disp = pricer.decode_dispatch(live, padded_rows=padded)

        # -- chunked prefill: TTFT -------------------------------------
        t_pre = pricer.prefill_tick(chunk, padded_rows=pad_pre)
        # a tick with a chunk in flight runs the prefill launch AND the
        # decode for everyone else
        t_mixed = t_pre + t_disp
        chunks_mean = max(math.ceil(uncached_mean / chunk), 1)
        chunks_p95 = max(math.ceil(uncached_p95 / chunk), 1)
        ttft = chunks_p95 * t_mixed + self.host_dispatch_s

        # -- the KV pool's HBM bill, at the strategy's storage dtype ----
        from flexflow_tpu.paged.quant import SCALE_BYTES, kv_dtype_info

        info = kv_dtype_info(s.kv_dtype)
        if info is None:
            kv_token_b = lay.kv_token_bytes
        else:
            kv_token_b = lay.kv_token_elems * info[1]
            if info[2]:  # quantized: scale sidecar amortized per page
                kv_token_b += -(-lay.kv_scale_elems * SCALE_BYTES // page)

        # -- request lifetime + throughput ------------------------------
        t_request = (chunks_mean * t_mixed
                     + (new_t / accepted) * t_disp)
        if occupancy > 0.9:
            # pool saturation: preemption + prefix recompute stalls
            pressure = 1.0 + 4.0 * (occupancy - 0.9)
            t_request *= pressure
            ttft *= pressure
        if offered > slots:
            # requests beyond the slot count wait for an earlier wave
            ttft += (offered / slots - 1.0) * t_request
        tokens_per_s = live * new_t / t_request

        return {
            "ttft_p95_s": ttft,
            "tokens_per_s": tokens_per_s,
            "hbm_bytes": lay.mem_bytes + pool_tokens * kv_token_b,
            "kv_token_bytes": float(kv_token_b),
            "pool_pages": float(pages),
            "pool_occupancy": occupancy,
            "live_rows": live,
            "padding_waste_ratio": padded / max(launch_rows, 1),
            "prefill_pad_rows": float(pad_pre),
            "expected_accepted_per_step": accepted,
            "decode_dispatch_s": t_disp,
            "prefill_tick_s": t_pre,
            "step_s": lay.step_s,
        }


# ---------------------------------------------------------------------------
# The knob table the existing drivers walk


class _Knob:
    """Stand-in node for StrategyTable rows — the drivers only read
    `.name`."""

    __slots__ = ("name",)

    def __init__(self, name: str):
        self.name = name


def default_space(*, max_len: int) -> Dict[str, List]:
    """The searched knob values. `spec` is a joint (width, depth) knob
    so half-set speculation can never be proposed; layout values are
    appended by the search when candidate meshes are given."""
    return {
        "page_size": [p for p in (8, 16, 32, 64, 128) if p <= max_len]
        or [max_len],
        "prefill_chunk": [c for c in (16, 32, 64, 128, 256) if c <= max_len]
        or [max_len],
        "spec": [(0, 0), (2, 2), (2, 4), (4, 4)],
        "pool_fraction": [1.0, 0.75, 0.5, 0.25],
        "kv_dtype": ["auto", "int8"],
        "host_tier_pages": [0, 256, 1024],
    }


def _knob_table(knobs: List[Tuple[str, List]]) -> StrategyTable:
    """A StrategyTable whose 'views' are knob values and whose cost
    tables are zero — the whole objective lives in the evaluate closure
    the drivers are handed, exactly how mcmc_optimize's fallback hands
    its summed-table evaluate to the same loop."""
    n = len(knobs)
    zeros = lambda: [[0.0] * len(vals) for _, vals in knobs]  # noqa: E731
    return StrategyTable(
        nodes=[_Knob(name) for name, _ in knobs],
        views=[list(vals) for _, vals in knobs],
        compute=zeros(), comm=zeros(), sync=zeros(), memory=zeros(),
        edges=[])


# ---------------------------------------------------------------------------
# Search result + driver


@dataclasses.dataclass
class ServeSearchResult:
    traffic: str
    slots: int
    max_len: int
    budget: int
    seed: int
    best: ServeStrategy
    best_objective: float
    best_metrics: Dict
    default: ServeStrategy
    default_objective: float
    default_metrics: Dict
    objective: ServeObjective
    trials: int
    calibration: Optional[Dict] = None
    layouts: List[Dict] = dataclasses.field(default_factory=list)
    # the pricer's traffic inputs, for provenance: the prompt moments
    # it priced with, the recorded arrival process (RecordedProfile
    # only), and where acceptance_rate came from (measured / default /
    # explicit) — a --replay search is auditable against its log
    stats: Optional[Dict] = None
    arrival: Optional[Dict] = None
    acceptance: Optional[Dict] = None
    # which evaluation backend scored the candidates: "closed-form"
    # (ServePricer algebra) or "ticksim" (event-driven replay of the
    # recorded arrival sequence — the --sim path)
    backend: str = "closed-form"

    @property
    def improvement(self) -> float:
        """Fractional objective win over the hand default (0.25 = 25%
        better)."""
        if self.default_objective <= 0:
            return 0.0
        return (self.default_objective - self.best_objective) \
            / self.default_objective

    def to_json(self) -> Dict:
        return {
            "traffic": self.traffic,
            "slots": self.slots,
            "max_len": self.max_len,
            "budget": self.budget,
            "seed": self.seed,
            "best": self.best.to_json(),
            "best_objective": self.best_objective,
            "best_metrics": self.best_metrics,
            "default": self.default.to_json(),
            "default_objective": self.default_objective,
            "default_metrics": self.default_metrics,
            "objective": self.objective.to_json(),
            "improvement": self.improvement,
            "trials": self.trials,
            "calibration": self.calibration,
            "layouts": self.layouts,
            "stats": self.stats,
            "arrival": self.arrival,
            "acceptance": self.acceptance,
            "backend": self.backend,
        }

    @classmethod
    def from_json(cls, d: Dict) -> "ServeSearchResult":
        return cls(
            traffic=d["traffic"], slots=d["slots"], max_len=d["max_len"],
            budget=d["budget"], seed=d["seed"],
            best=ServeStrategy.from_json(d["best"]),
            best_objective=d["best_objective"],
            best_metrics=d["best_metrics"],
            default=ServeStrategy.from_json(d["default"]),
            default_objective=d["default_objective"],
            default_metrics=d["default_metrics"],
            objective=ServeObjective.from_json(d["objective"]),
            trials=d["trials"], calibration=d.get("calibration"),
            layouts=d.get("layouts", []), stats=d.get("stats"),
            arrival=d.get("arrival"), acceptance=d.get("acceptance"),
            backend=d.get("backend", "closed-form"))


def build_pricer(ff=None, *, graph=None, cost=None, traffic="smoke",
                 slots: int = 4, max_len: int = 512,
                 acceptance_rate: Optional[float] = None,
                 calibration=None,
                 host_dispatch_s: float = HOST_DISPATCH_SECONDS,
                 seed: int = 0) -> ServePricer:
    """A ServePricer for one traffic profile WITHOUT running a search —
    the entry `servesearch simulate` and the sim-accuracy tests share.
    Same resolution rules as search_serve_strategy: a RecordedProfile's
    measured acceptance wins over the prior, and a fresh calibration
    report threads its measured tick scales into every price."""
    if ff is not None:
        from flexflow_tpu.search.api import _cost_model

        graph = ff.graph
        cost = _cost_model(ff.mesh, ff.config)
    if graph is None or cost is None:
        raise ValueError("build_pricer needs ff= or graph=+cost=")

    from flexflow_tpu.search import traffic as traffic_mod

    profile = traffic_mod.get_profile(traffic)
    stats = profile.prompt_stats()
    if acceptance_rate is None:
        measured = (profile.measured_acceptance()
                    if hasattr(profile, "measured_acceptance") else None)
        acceptance_rate = (float(measured) if measured is not None
                           else DEFAULT_ACCEPTANCE_RATE)
    tick_scale_fn = None
    if calibration is not None:
        report = load_calibration(calibration)
        if report is not None:
            from flexflow_tpu.search.measured import MeasuredCostModel

            if not isinstance(cost, MeasuredCostModel):
                cost = MeasuredCostModel(
                    machine=cost.machine, axis_sizes=dict(cost.axis_sizes),
                    backward_factor=cost.backward_factor,
                    param_parallel=cost.param_parallel,
                    attr_parallel=cost.attr_parallel)
            cost.set_tick_calibration(report)
            tick_scale_fn = cost.tick_scale
    priced = price_layouts(graph, cost, [dict(cost.axis_sizes)], seed=seed)
    return ServePricer(priced, stats, slots=slots, max_len=max_len,
                       acceptance_rate=acceptance_rate,
                       host_dispatch_s=host_dispatch_s,
                       tick_scale=tick_scale_fn)


def search_serve_strategy(
    ff=None, *, graph=None, cost=None, traffic="smoke",
    objective: Optional[ServeObjective] = None, budget: int = 200,
    alpha: float = 0.05, seed: int = 0, slots: int = 4,
    max_len: int = 512, default: Optional[ServeStrategy] = None,
    space: Optional[Dict[str, List]] = None,
    layouts: Optional[Sequence[Dict[str, int]]] = None,
    inner_budget: int = 0, calibration=None,
    acceptance_rate: Optional[float] = None,
    host_dispatch_s: float = HOST_DISPATCH_SECONDS, verbose: bool = False,
    sim: bool = False,
) -> ServeSearchResult:
    """Search the ServeStrategy space for `traffic`, minimizing
    `objective` (default: TTFT p95 + seconds/token at the machine's HBM
    budget). Pass a compiled `ff`, or a (graph, cost) pair directly.

    `layouts` adds candidate serving-mesh axis layouts; with
    `inner_budget` > 0 each is shard-searched by the existing
    mcmc_optimize before pricing. `calibration` takes an `fftrace
    calibrate` report (path or dict); fresh reports are threaded through
    MeasuredCostModel.set_tick_calibration into every tick price, stale
    ones refused with a warning (load_calibration). Fixed `seed` makes
    the whole search deterministic.

    `acceptance_rate=None` (default) resolves automatically: a
    RecordedProfile's MEASURED spec acceptance when `traffic` carries
    one (the --replay path), else the 0.6 prior. An explicit value
    always wins. The result's `acceptance` dict records which.

    `sim=True` evaluates each candidate with the event-driven
    `ticksim.TickSimulator` — replaying the profile's recorded arrival
    sequence through the simulated tick loop — instead of the
    closed-form ServePricer, IF the profile carries an arrival trace
    (a RecordedProfile / --replay log); otherwise it falls back to the
    closed form with a warning. The result's `backend` field records
    which backend scored the winner."""
    if ff is not None:
        from flexflow_tpu.search.api import _cost_model

        graph = ff.graph
        cost = _cost_model(ff.mesh, ff.config)
    if graph is None or cost is None:
        raise ValueError("search_serve_strategy needs ff= or graph=+cost=")

    from flexflow_tpu.search import traffic as traffic_mod

    profile = traffic_mod.get_profile(traffic)
    stats = profile.prompt_stats()
    arrival = (profile.arrival_stats()
               if hasattr(profile, "arrival_stats") else None)

    # acceptance_rate=None -> measured from the profile when the log
    # recorded drafting (RecordedProfile.measured_acceptance), else the
    # prior; an explicit value always wins
    if acceptance_rate is None:
        measured = (profile.measured_acceptance()
                    if hasattr(profile, "measured_acceptance") else None)
        if measured is not None:
            acceptance_rate, acceptance_src = float(measured), "measured"
        else:
            acceptance_rate, acceptance_src = (
                DEFAULT_ACCEPTANCE_RATE, "default")
    else:
        acceptance_rate, acceptance_src = float(acceptance_rate), "explicit"

    # -- calibration hand-off -------------------------------------------
    tick_scale_fn = None
    cal_summary = None
    if calibration is not None:
        report = load_calibration(calibration)
        if report is None:
            cal_summary = {"used": False, "reason": "stale-or-unstamped"}
        else:
            from flexflow_tpu.search.measured import MeasuredCostModel

            if not isinstance(cost, MeasuredCostModel):
                cost = MeasuredCostModel(
                    machine=cost.machine, axis_sizes=dict(cost.axis_sizes),
                    backward_factor=cost.backward_factor,
                    param_parallel=cost.param_parallel,
                    attr_parallel=cost.attr_parallel)
            cost.set_tick_calibration(report)
            tick_scale_fn = cost.tick_scale
            cal_summary = {
                "used": True,
                "version": report.get("version"),
                "created_at": report.get("created_at"),
                "shapes": len(report.get("tick_scales", {})),
            }

    # -- price the candidate mesh layouts -------------------------------
    layout_dicts = ([dict(cost.axis_sizes)] if layouts is None
                    else [dict(axes) for axes in layouts])
    priced = price_layouts(graph, cost, layout_dicts,
                           inner_budget=inner_budget, seed=seed)

    if objective is None:
        objective = ServeObjective(
            hbm_budget_bytes=cost.machine.memory_per_chip())

    pricer = ServePricer(priced, stats, slots=slots, max_len=max_len,
                         acceptance_rate=acceptance_rate,
                         host_dispatch_s=host_dispatch_s,
                         tick_scale=tick_scale_fn)

    # -- evaluation backend: closed-form algebra or event replay --------
    backend = "closed-form"
    simulator = None
    if sim:
        from flexflow_tpu.search.ticksim import (
            TickSimulator,
            has_arrival_trace,
        )

        if has_arrival_trace(profile):
            simulator = TickSimulator(pricer)
            backend = "ticksim"
        else:
            logger.warning(
                "servesearch sim=True: profile %r carries no arrival "
                "trace (not a recorded reqlog) — falling back to the "
                "closed-form pricer", profile.name)

    # -- knob table + start point ---------------------------------------
    if default is None:
        default = ServeStrategy()
    default = dataclasses.replace(
        default, page_size=min(default.page_size, max_len),
        prefill_chunk=min(default.prefill_chunk, max_len))
    values = default_space(max_len=max_len) if space is None else \
        {k: list(v) for k, v in space.items()}
    defaults = {
        "page_size": default.page_size,
        "prefill_chunk": default.prefill_chunk,
        "spec": (default.spec_width, default.spec_depth),
        "pool_fraction": default.pool_fraction,
        "kv_dtype": default.kv_dtype,
        "host_tier_pages": default.host_tier_pages,
    }
    for name, dval in defaults.items():
        vals = values.setdefault(name, [dval])
        if dval not in vals:
            vals.insert(0, dval)
    knobs = [(name, values[name]) for name in
             ("page_size", "prefill_chunk", "spec", "pool_fraction",
              "kv_dtype", "host_tier_pages")]
    if len(priced) > 1:
        knobs.append(("mesh", [lay.mesh_key for lay in priced]))
    table = _knob_table(knobs)

    names = [name for name, _ in knobs]

    def to_strategy(assign) -> ServeStrategy:
        kv = {name: table.views[i][k]
              for i, (name, k) in enumerate(zip(names, assign))}
        w, d = kv.pop("spec")
        return ServeStrategy(spec_width=w, spec_depth=d,
                             mesh=kv.pop("mesh", default.mesh), **kv)

    cache: Dict[Tuple[int, ...], Tuple[float, Optional[Dict]]] = {}

    def evaluate(assign) -> float:
        key = tuple(assign)
        hit = cache.get(key)
        if hit is None:
            strat = to_strategy(assign)
            try:
                strat.validate(max_len=max_len)
            except ValueError:
                hit = (INVALID_OBJECTIVE, None)
            else:
                if simulator is not None:
                    m = simulator.simulate(strat, profile, seed=seed).metrics
                else:
                    m = pricer.metrics(strat)
                hit = (objective.value(m), m)
            cache[key] = hit
        return hit[0]

    start = [vals.index(defaults[name]) if name in defaults else 0
             for name, vals in knobs]
    default_cost = evaluate(start)
    default_metrics = cache[tuple(start)][1]
    default_strategy = to_strategy(start)

    # -- the existing drivers: anneal, then coordinate descent ----------
    from flexflow_tpu.search.mcmc import anneal_assignment

    best_assign, _ = anneal_assignment(table, start, evaluate,
                                       budget=budget, alpha=alpha,
                                       seed=seed, verbose=verbose)
    best_assign = list(best_assign)
    best_cost = coordinate_descent(table, best_assign, evaluate, sweeps=2)
    best_metrics = cache[tuple(best_assign)][1]
    best_strategy = to_strategy(best_assign)
    if verbose:
        logger.info("servesearch[%s]: %s -> %.6f (default %.6f, %d trials)",
                    profile.name, best_strategy.describe(), best_cost,
                    default_cost, len(cache))

    return ServeSearchResult(
        traffic=profile.name, slots=slots, max_len=max_len, budget=budget,
        seed=seed, best=best_strategy, best_objective=best_cost,
        best_metrics=best_metrics, default=default_strategy,
        default_objective=default_cost, default_metrics=default_metrics,
        objective=objective, trials=len(cache), calibration=cal_summary,
        layouts=[lay.summary() for lay in priced], stats=stats,
        arrival=arrival,
        acceptance={"rate": acceptance_rate, "source": acceptance_src},
        backend=backend)
