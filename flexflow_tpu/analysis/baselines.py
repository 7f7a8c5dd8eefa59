"""BASELINE configs for the static analyzer (and coverage tooling).

The single home of the config list that used to live in
tools/rule_coverage.py: each entry is (name, build(ff), mesh_shape) for
the round's five target configs (AlexNet, ResNet-50, BERT-base, Llama
TP+DP, Mixtral EP; SURVEY.md) plus InceptionV3 (where the concat/merge algebra
demonstrably fires) plus a seq-parallel llama variant that exercises the
ring/ulysses comm-spec cross-check. `build_baseline_subjects()` builds
the PCGs with their canonical hand strategies (default DP where no hand
strategy exists) — the subjects `fflint --strict` must run clean on.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple


def baseline_configs() -> List[Tuple[str, Callable, Dict[str, int]]]:
    """(name, build(ff) -> None, mesh_shape) per BASELINE config plus
    InceptionV3; small layer counts — coverage and consistency depend on
    structure, not depth."""
    from flexflow_tpu.models.alexnet import build_alexnet_cifar10
    from flexflow_tpu.models.bert import BertConfig, build_bert
    from flexflow_tpu.models.inception import build_inception_v3
    from flexflow_tpu.models.llama import LlamaConfig, build_llama
    from flexflow_tpu.models.mixtral import MixtralConfig, build_mixtral
    from flexflow_tpu.models.resnet import build_resnet50

    def alexnet(ff):
        build_alexnet_cifar10(ff, batch_size=8)

    def resnet(ff):
        build_resnet50(ff, batch_size=8, classes=100)

    def bert(ff):
        build_bert(ff, BertConfig(vocab_size=512, hidden=64, layers=2,
                                  heads=4, intermediate=128),
                   batch_size=8, seq_len=64)

    def llama(ff):
        build_llama(ff, LlamaConfig(vocab_size=512, dim=64, layers=2,
                                    heads=4, kv_heads=2, hidden=128,
                                    rope_theta=10000.0),
                    batch_size=8, seq_len=128)

    def mixtral(ff):
        build_mixtral(ff, MixtralConfig.tiny(), batch_size=8, seq_len=32)

    def inception(ff):
        # 75px input keeps the tiny-config search fast; every inception
        # block's concat-of-parallel-branches structure is preserved
        build_inception_v3(ff, batch_size=8, classes=32, image_size=75)

    return [
        ("alexnet_cifar10", alexnet, {"data": 2, "model": 4}),
        ("resnet50", resnet, {"data": 2, "model": 4}),
        ("bert_base", bert, {"data": 2, "model": 4}),
        ("llama_tp_dp", llama, {"data": 2, "seq": 2, "model": 2}),
        ("mixtral_ep", mixtral, {"data": 2, "expert": 4}),
        ("inception_v3", inception, {"data": 2, "model": 4}),
    ]


def _llama_tiny_cfg():
    from flexflow_tpu.models.llama import LlamaConfig

    return LlamaConfig(vocab_size=512, dim=64, layers=2, heads=4,
                       kv_heads=2, hidden=128, rope_theta=10000.0)


def build_graph(build: Callable, mesh_shape: Dict[str, int]):
    """Build one config's PCG (no search, no compile, no mesh needed)."""
    from flexflow_tpu import FFConfig, FFModel

    ff = FFModel(FFConfig(batch_size=8, mesh_shape=dict(mesh_shape)))
    build(ff)
    ff.graph.infer_shapes()
    return ff.graph


def _hand_strategy(name: str) -> Optional[Dict]:
    """The shipped hand strategy for a config (None = default DP)."""
    if name == "bert_base":
        from flexflow_tpu.models.bert import (
            BertConfig,
            bert_attribute_parallel_strategy,
        )

        return bert_attribute_parallel_strategy(
            BertConfig(vocab_size=512, hidden=64, layers=2, heads=4,
                       intermediate=128))
    if name == "llama_tp_dp":
        from flexflow_tpu.models.llama import llama_tp_strategy

        return llama_tp_strategy(_llama_tiny_cfg())
    if name == "mixtral_ep":
        from flexflow_tpu.models.mixtral import (
            MixtralConfig,
            mixtral_ep_strategy,
        )

        return mixtral_ep_strategy(MixtralConfig.tiny())
    return None


SP_SUBJECT_NAMES = ("llama_sp_ring", "llama_sp_ulysses")

_SP_MESH = {"data": 2, "seq": 2, "model": 2}


def known_subject_names() -> List[str]:
    return [name for name, _, _ in baseline_configs()] + list(SP_SUBJECT_NAMES)


def _subject_recipe(name: str):
    """(build(ff), mesh_shape, strategy(graph)) for one subject name —
    the single home of per-config construction, shared by
    build_baseline_subjects (graphs for the consistency pass) and
    build_baseline_executor (compiled executors for hloaudit), so the
    two passes can never silently audit different subjects."""
    from flexflow_tpu.models.llama import build_llama, llama_tp_strategy
    from flexflow_tpu.search.api import space_dp_strategy

    if name not in known_subject_names():
        raise ValueError(f"unknown BASELINE config name {name!r}; known: "
                         f"{known_subject_names()}")
    if name in SP_SUBJECT_NAMES:
        seq_mode = "ring" if name.endswith("ring") else "ulysses"

        def build(ff):
            build_llama(ff, _llama_tiny_cfg(), batch_size=8, seq_len=128,
                        use_ring_attention=True, seq_mode=seq_mode)

        return build, dict(_SP_MESH), lambda graph: llama_tp_strategy(
            _llama_tiny_cfg(), seq_parallel=True)

    _, build, mesh_shape = next(
        c for c in baseline_configs() if c[0] == name)

    def strategy_for(graph):
        hand = _hand_strategy(name)
        return (hand if hand is not None
                else space_dp_strategy(graph, mesh_shape))

    return build, dict(mesh_shape), strategy_for


def build_baseline_subjects(names: Optional[List[str]] = None):
    """[(name, graph, strategy, axis_sizes)] for the consistency pass:
    every BASELINE config under its canonical strategy (hand strategy
    where one ships, default DP otherwise), plus `llama_sp_ring` /
    `llama_sp_ulysses` — seq-parallel ring-attention builds whose views
    must agree with the exchange the lowering emits."""
    if names:
        unknown = sorted(set(names) - set(known_subject_names()))
        if unknown:
            # a typo must not silently validate NOTHING and report clean
            raise ValueError(
                f"unknown BASELINE config name(s) {unknown}; known: "
                f"{known_subject_names()}")
    subjects = []
    for name in known_subject_names():
        if names and name not in names:
            continue
        build, mesh_shape, strategy_for = _subject_recipe(name)
        graph = build_graph(build, mesh_shape)
        subjects.append((name, graph, strategy_for(graph), mesh_shape))
    return subjects


def build_baseline_executor(name: str):
    """Compile ONE BASELINE config end-to-end — FFModel.compile under its
    canonical strategy on the local (8-device CPU) mesh — and return
    (executor, graph, strategy, axis_sizes). This is the hloaudit entry:
    the executor's lowered_modules() are the ground-truth artifacts the
    cost model is audited against; _subject_recipe guarantees it is the
    SAME config/strategy the consistency pass checks
    (build_baseline_subjects)."""
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, LossType

    build, mesh_shape, strategy_for = _subject_recipe(name)
    ff = FFModel(FFConfig(batch_size=8, mesh_shape=dict(mesh_shape)))
    build(ff)
    ff.graph.infer_shapes()
    strategy = strategy_for(ff.graph)
    ff.compile(optimizer=AdamOptimizer(lr=1e-4),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               strategy=strategy)
    return ff.executor, ff.graph, strategy, dict(mesh_shape)
