"""Runtime configuration.

Reference analog: `FFConfig` (include/flexflow/config.h:92-160) and its argv
parser (`FFModel::parse_args`, model.cc:3556-3719). GPU-count/Legion flags
become device-mesh configuration; the search/profiling/fusion flags carry
over with the same names where they make sense on TPU.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

from flexflow_tpu.ffconst import CompMode, DataType, ParamSyncType


@dataclasses.dataclass
class FFConfig:
    # ---- training loop ----
    batch_size: int = 64
    epochs: int = 1
    seed: int = 42
    # truncated-sequence iteration config (reference FFIterationConfig
    # config.h:162-167): forward/backward may run a shorter seq length
    seq_length: Optional[int] = None

    # ---- devices / mesh ----
    # number of devices to use (None = all visible jax devices); the
    # reference analog is `-ll:gpu` × numNodes
    num_devices: Optional[int] = None
    # explicit mesh shape: ordered {axis_name: size}; None = let compile()
    # derive it from the chosen strategy (e.g. {"data": 8} for pure DP)
    mesh_shape: Optional[Dict[str, int]] = None

    # ---- numerics ----
    compute_dtype: DataType = DataType.FLOAT
    param_sync: ParamSyncType = ParamSyncType.PSUM
    # storage dtype of the weights compile() draws (None: float32 masters,
    # the training default). A served checkpoint is stored as published
    # ("bfloat16" where its config says torch_dtype bfloat16): passed to
    # Executor.init_params(weight_dtype=), use sites cast to the compute
    # dtype, and a model too large for float32 masters fits. This is
    # STORAGE AT INIT, for a model that never trains. A trainable model
    # (None) that is served keeps its masters and needs no setting: its
    # servers launch with FFModel.serving_params(), the leaves declared
    # narrower than stored converted once; a tree already stored here is
    # handed to them as it is
    weight_dtype: Optional[str] = None

    # ---- strategy search (reference model.cc:3599-3719 flags) ----
    search_budget: int = 0
    search_alpha: float = 1.05
    # search already requires search_budget > 0; this flag force-disables it
    # (reference --only-data-parallel, model.cc:3609 — off by default there too)
    only_data_parallel: bool = False
    # SOAP dimension gates for the search space (reference
    # --enable-parameter-parallel / --enable-attribute-parallel,
    # model.cc:3613-3617). The reference defaults these off; TPU-native
    # default is on — weight/head sharding is the normal operating mode,
    # set False to restrict the search to sample parallelism.
    enable_parameter_parallel: bool = True
    enable_attribute_parallel: bool = True
    # per-op submesh placement (reference MachineView{start_device_id,
    # stride}, machine_view.h:14-96): split the data axis into
    # data x data_sub so ops whose batch dim cannot divide the full data
    # group shard over a DEVICE SUBSET (replicated across the rest)
    # instead of degrading to full replication; the view space offers
    # both the full-group and subset points (search/space.py)
    enable_submesh: bool = False
    memory_search: bool = False
    # search for a machine bigger than the one running (reference
    # --search-num-workers, model.cc:3692); extra chips extend `data`
    search_num_devices: Optional[int] = None
    machine_model_file: Optional[str] = None
    # measure real per-op shard times on the local device and use them in
    # the search cost model (reference measure_operator_cost discipline,
    # simulator.cc:537); cache file avoids re-measuring across runs
    measure_costs: bool = False
    # after the model-based search, compile the top-k candidate strategies'
    # REAL train steps and keep the empirically fastest (SURVEY §7: XLA
    # fusion makes op-sum != program time, so the final ranking is timed,
    # not modeled). 0/1 = off; costs k-1 extra compiles at compile() time.
    validate_top_k: int = 0
    measure_cache_file: Optional[str] = None
    # cost strategies with the native event-driven simulator instead of the
    # summed-table estimate (Simulator::simulate_runtime analog): the Unity
    # search ranks every candidate with the PER-DEVICE task simulator
    # (search/eventsim.py -> ffsim_tasksim_*), and the playoff pool re-rank
    # / MCMC objective use it too. Default ON; degrades to the serial sum
    # when libffsim is unavailable. --no-simulator disables.
    use_simulator: bool = True
    import_strategy_file: Optional[str] = None
    export_strategy_file: Optional[str] = None
    export_strategy_computation_graph_file: Optional[str] = None
    include_costs_dot_graph: bool = False

    # periodic training checkpoints (net-new vs the reference, SURVEY.md
    # §5.4): every `checkpoint_every` steps fit() writes
    # checkpoint_dir/step_N (orbax if available, else npz) + latest.json
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0

    # ---- execution ----
    profiling: bool = False
    # capture a jax profiler trace of fit() into this dir (view with
    # tensorboard / xprof — the -lg:prof analog, SURVEY.md §5.1)
    profiler_trace_dir: Optional[str] = None
    # jax transfer guard level during fit ("log" | "disallow"): surfaces
    # accidental host<->device transfers in the step loop (the
    # race-detection analog, SURVEY.md §5.2 — purity is by construction,
    # transfers are the remaining foot-gun)
    transfer_guard: Optional[str] = None
    # rematerialization: "attention" wraps attention ops in jax.checkpoint so
    # S×S probs are recomputed in backward instead of saved (HBM for FLOPs —
    # net-new vs the reference, which has no remat); "hidden" instead
    # recomputes MLP hidden activations (SwiGLU gate/up/silu/mul, expanding
    # Linear+activation chains) — the dominant saved-activation HBM at LLM
    # shapes for ~2% extra FLOPs; "none" disables
    remat: str = "attention"
    # op fusion: on TPU XLA fuses inside one jitted program for free; this
    # flag only controls whether the PCG keeps explicit FusedOp groups for
    # search costing (reference --fusion, model.cc:2965)
    perform_fusion: bool = False
    comp_mode: CompMode = CompMode.TRAINING
    # donate params/opt-state buffers to the jitted step (halves HBM)
    donate_buffers: bool = True

    # populated by FFModel at compile time
    _devices: Optional[List] = dataclasses.field(default=None, repr=False)

    @property
    def devices(self) -> List:
        if self._devices is None:
            import jax

            devs = jax.devices()
            n = self.num_devices or len(devs)
            self._devices = devs[:n]
        return self._devices

    @property
    def workers_per_node(self) -> int:
        return len(self.devices)

    @classmethod
    def from_args(cls, argv: Sequence[str]) -> "FFConfig":
        """Parse reference-style command-line flags (model.cc:3556-3719)."""
        cfg = cls()
        args = list(argv)
        i = 0

        def take() -> str:
            nonlocal i
            i += 1
            if i >= len(args):
                raise ValueError(f"flag {args[i - 1]!r} requires a value")
            return args[i]

        while i < len(args):
            a = args[i]
            if a in ("-b", "--batch-size"):
                cfg.batch_size = int(take())
            elif a in ("-e", "--epochs"):
                cfg.epochs = int(take())
            elif a == "--seed":
                cfg.seed = int(take())
            elif a == "--checkpoint-dir":
                cfg.checkpoint_dir = take()
            elif a == "--checkpoint-every":
                cfg.checkpoint_every = int(take())
            elif a in ("--devices", "-ll:gpu", "-ll:tpu"):
                cfg.num_devices = int(take())
            elif a == "--mesh":
                # e.g. --mesh data=2,model=4 (net-new: explicit mesh axes)
                cfg.mesh_shape = {
                    k: int(v)
                    for k, v in (p.split("=") for p in take().split(","))
                }
            elif a == "--budget" or a == "--search-budget":
                cfg.search_budget = int(take())
            elif a == "--validate-top-k":
                cfg.validate_top_k = int(take())
            elif a == "--alpha" or a == "--search-alpha":
                cfg.search_alpha = float(take())
            elif a == "--only-data-parallel":
                cfg.only_data_parallel = True
            elif a == "--search":
                cfg.only_data_parallel = False
            elif a == "--enable-parameter-parallel":
                cfg.enable_parameter_parallel = True
            elif a == "--enable-attribute-parallel":
                # the reference sets parameter-parallel here too (noted as an
                # upstream bug in SURVEY.md §2.3); we keep them independent
                cfg.enable_attribute_parallel = True
            elif a == "--enable-submesh":
                cfg.enable_submesh = True
            elif a == "--simulator":
                cfg.use_simulator = True
            elif a == "--no-simulator":
                cfg.use_simulator = False
            elif a == "--profiler-trace":
                cfg.profiler_trace_dir = take()
            elif a == "--transfer-guard":
                cfg.transfer_guard = take()
            elif a == "--memory-search":
                cfg.memory_search = True
            elif a == "--search-num-devices":
                cfg.search_num_devices = int(take())
            elif a == "--machine-model-file":
                cfg.machine_model_file = take()
            elif a == "--import-strategy" or a == "--import":
                cfg.import_strategy_file = take()
            elif a == "--export-strategy" or a == "--export":
                cfg.export_strategy_file = take()
            elif a == "--compgraph":
                cfg.export_strategy_computation_graph_file = take()
            elif a == "--include-costs-dot-graph":
                cfg.include_costs_dot_graph = True
            elif a == "--profiling":
                cfg.profiling = True
            elif a == "--fusion":
                cfg.perform_fusion = True
            elif a == "--inference":
                cfg.comp_mode = CompMode.INFERENCE
            # unknown flags are ignored (the reference passes extras to Legion)
            i += 1
        return cfg
