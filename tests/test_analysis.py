"""fflint static-analysis subsystem (flexflow_tpu.analysis): pass
registry, the eight passes (consistency / rulesat / hostsync /
hloaudit / poolcheck / shapecheck / racecheck / numcheck), the
seeded-defect regression fixtures from
ISSUE 3 (a misdeclared cost-model comm-spec reintroducing the ulysses
h_deg bug shape, an unsatisfiable corpus rule, a host-sync in a decode
loop), ISSUE 4 (a zeroed priced comm event the lowered-HLO diff must
flag with the node named, a config whose priced memory exceeds the
machine model's HBM budget), ISSUE 9 (three injected pool defects — a
dropped refcount decrement in defrag, an in-place write to a shared COW
tail, a spec scratch page registered pre-commit — each of which the
poolcheck model checker must catch with a named finding and a
replayable minimal counterexample trace) and ISSUE 14 (an unclamped
launch width that must produce shape-space-unbounded with its taint
chain, plus a deliberately shrunk catalog check_soundness must fail —
the live-serving half of that gate runs in
tests/test_shapecheck_gate.py) and ISSUE 18 (three injected
concurrency defects — a dropped-lock host-tier mutation, an inverted
tier-vs-scheduler lock acquisition order, a prefill->decode handoff
that submits the same request twice — which racecheck's lint arm and
bounded interleaving model checker must each catch with a named
finding, the dynamic ones with minimal replayable interleaving
traces) and ISSUE 19 (numcheck's seeded numerics defects — a dropped
scale-sidecar read, a forced f64 promotion with its derivation chain,
an HLO module whose dots accumulate narrower than the declared dtype
plan — plus the budget-catalog arm), strategy-file import validation,
and the CLI strict gate tier-1 rides on."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from flexflow_tpu.analysis import (
    AnalysisContext,
    Report,
    available_passes,
    run_passes,
)
from flexflow_tpu.analysis.consistency import check_strategy
from flexflow_tpu.search.cost_model import CostModel
from flexflow_tpu.search.machine_model import TPUMachineModel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _llama_sp_subject(seq_mode="ulysses", heads=8, kv_heads=2):
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.llama import (
        LlamaConfig,
        build_llama,
        llama_tp_strategy,
    )

    cfg = LlamaConfig(vocab_size=256, dim=64, layers=1, heads=heads,
                      kv_heads=kv_heads, hidden=128, rope_theta=10000.0)
    mesh_shape = {"data": 2, "seq": 2, "model": 2}
    ff = FFModel(FFConfig(batch_size=8, mesh_shape=mesh_shape))
    build_llama(ff, cfg, batch_size=8, seq_len=128,
                use_ring_attention=True, seq_mode=seq_mode)
    ff.graph.infer_shapes()
    return ff.graph, llama_tp_strategy(cfg, seq_parallel=True), mesh_shape


def _cost_model(axis_sizes):
    ndev = 1
    for s in axis_sizes.values():
        ndev *= s
    return CostModel(TPUMachineModel.make("v5e", ndev), dict(axis_sizes))


def test_pass_registry_has_the_three_passes():
    assert set(available_passes()) >= {"consistency", "rulesat", "hostsync"}
    report = run_passes(["hostsync"], AnalysisContext(src_paths=[]))
    assert isinstance(report, Report)
    assert report.findings == []


# ---------------------------------------------------------------------------
# consistency pass


def test_consistency_clean_on_seq_parallel_llama():
    graph, strategy, axis_sizes = _llama_sp_subject("ulysses")
    findings = check_strategy(graph, strategy, axis_sizes,
                              cost_model=_cost_model(axis_sizes))
    assert [f for f in findings if f.severity == "error"] == []


def test_consistency_flags_divisibility_with_named_node():
    """kv_heads=2 sharded 4-way: execution replicates (prune_spec) while
    the cost model prices the shard — named-node warning (warning, not
    error: the shipped llama_tp_strategy deliberately leans on this
    degradation, so only --strict gates it)."""
    from flexflow_tpu.parallel.sharding import ShardingView

    graph, strategy, _ = _llama_sp_subject("ring")
    axis_sizes = {"data": 2, "seq": 2, "model": 4}
    strategy = dict(strategy)
    strategy["l0_attn"] = ShardingView(
        output_specs=strategy["l0_attn"].output_specs,
        weight_specs={"wk": ((), ("model",), ())},
    )
    hits = [f for f in check_strategy(graph, strategy, axis_sizes)
            if f.code == "degree-divides"]
    assert hits, "non-dividing shard not flagged"
    assert all(f.severity == "warning" for f in hits)
    assert any("l0_attn" in f.where for f in hits)
    assert any("size 2" in f.message and "4-way" in f.message for f in hits)


def test_consistency_flags_gqa_grouping_and_duplicate_axis():
    from flexflow_tpu.parallel.sharding import ShardingView

    graph, strategy, axis_sizes = _llama_sp_subject("ring", heads=8,
                                                    kv_heads=8)
    strategy = dict(strategy)
    # wq heads over model but wo heads over seq: partial sums would mix
    # head groups
    strategy["l0_attn"] = ShardingView(
        output_specs=strategy["l0_attn"].output_specs,
        weight_specs={"wq": ((), ("model",), ()),
                      "wo": (("seq",), (), ())},
    )
    findings = check_strategy(graph, strategy, axis_sizes)
    assert any(f.code == "gqa-grouping" and "l0_attn" in f.where
               for f in findings)
    # duplicate axis on two dims of one spec
    strategy["l0_gate"] = ShardingView(
        ((("model",), (), ("model",)),))
    findings = check_strategy(graph, strategy, axis_sizes)
    assert any(f.code == "duplicate-axis" and "l0_gate" in f.where
               for f in findings)


def test_consistency_flags_stale_strategy():
    graph, _, axis_sizes = _llama_sp_subject("ring")
    from flexflow_tpu.parallel.sharding import ShardingView

    stale = {"no_such_node": ShardingView(((("data",), (), ()),))}
    findings = check_strategy(graph, stale, axis_sizes)
    errs = [f for f in findings if f.code == "stale-strategy"]
    assert errs and errs[0].severity == "error"
    assert "no_such_node" in errs[0].message


class _BuggyCostModel(CostModel):
    """Regression fixture: the round-5 ulysses h_deg bug shape — the
    exchange priced with h_deg derived from the VIEW's wo sharding
    (unsharded wo => h_deg=1 => kv priced unrepeated) instead of the mesh
    head axis the lowering reads."""

    def attention_comm_spec(self, graph, node, view):
        from flexflow_tpu.parallel.comm_spec import CommStep, ulysses_plan

        steps = super().attention_comm_spec(graph, node, view)
        wo = view.weight_specs.get("wo")
        h_deg_view = 1
        if wo and wo[0]:
            for a in wo[0]:
                h_deg_view *= self.axis_sizes.get(a, 1)
        out = []
        for st in steps:
            a = node.attrs
            o = node.outputs[0]
            b, s = o.dims[0].size, o.dims[1].size
            dt = o.dtype.size_bytes
            q_bytes = b * s * a.num_heads * a.kdim * dt
            if st.kind == "all_to_all" and st.nbytes > q_bytes:
                deg = 1
                for ax in st.axes:
                    deg *= self.axis_sizes.get(ax, 1)
                plan = ulysses_plan(a.num_heads, a.num_kv, h_deg_view, deg)
                kv_ex = 2 * b * s * plan.kv_heads_exchanged * a.kdim * dt
                out.append(CommStep(st.kind, st.axes, q_bytes + kv_ex))
            else:
                out.append(st)
        return out


def test_consistency_flags_misdeclared_comm_spec():
    """Seeded defect 1 (ISSUE 3): GQA heads=8/kv=2 on a seq=2 x model=2
    mesh with wo unsharded in the view — the lowering repeats kv for the
    exchange (mesh h_deg=2 gives local_kv=1, indivisible by seq degree)
    but the buggy model prices unrepeated kv. The comm-spec cross-check
    must flag it; the correct model must be clean."""
    from flexflow_tpu.parallel.sharding import ShardingView

    graph, strategy, axis_sizes = _llama_sp_subject("ulysses", heads=8,
                                                    kv_heads=2)
    strategy = dict(strategy)
    # keep the seq-sharded activations but drop the wo sharding — the
    # shape where wo-derived h_deg diverges from the mesh head axis
    old = strategy["l0_attn"]
    strategy["l0_attn"] = ShardingView(
        output_specs=old.output_specs,
        weight_specs={k: v for k, v in old.weight_specs.items()
                      if k != "wo"},
        input_specs=old.input_specs,
    )
    clean = [f for f in check_strategy(graph, strategy, axis_sizes,
                                       cost_model=_cost_model(axis_sizes))
             if f.code == "comm-spec-mismatch"]
    assert clean == [], [f.message for f in clean]
    buggy = _BuggyCostModel(TPUMachineModel.make("v5e", 8),
                            dict(axis_sizes))
    flagged = [f for f in check_strategy(graph, strategy, axis_sizes,
                                         cost_model=buggy)
               if f.code == "comm-spec-mismatch"]
    assert flagged, "buggy comm-spec not caught"
    assert flagged[0].severity == "error"
    assert "l0_attn" in flagged[0].where
    assert "lowering emits" in flagged[0].message


def test_consistency_flags_unpriced_mesh_driven_ring_exchange():
    """A RING_ATTENTION node on a seq>1 mesh always ppermutes (the
    lowering reads the mesh, not the view); a view that does not shard
    the sequence prices zero comm — the cross-check catches the
    underpricing."""
    from flexflow_tpu.models.llama import LlamaConfig, llama_tp_strategy

    graph, _, axis_sizes = _llama_sp_subject("ring")
    cfg = LlamaConfig(vocab_size=256, dim=64, layers=1, heads=8,
                      kv_heads=2, hidden=128, rope_theta=10000.0)
    strategy = llama_tp_strategy(cfg, seq_parallel=False)  # no seq shard
    flagged = [f for f in check_strategy(graph, strategy, axis_sizes,
                                         cost_model=_cost_model(axis_sizes))
               if f.code == "comm-spec-mismatch"]
    assert flagged and "ppermute" in flagged[0].message
    # the same underpricing with the attention node simply OMITTED from
    # the strategy (no view at all -> cost model prices zero comm)
    no_attn = {k: v for k, v in strategy.items() if k != "l0_attn"}
    flagged = [f for f in check_strategy(graph, no_attn, axis_sizes,
                                         cost_model=_cost_model(axis_sizes))
               if f.code == "comm-spec-mismatch"]
    assert flagged and "l0_attn" in flagged[0].where


def test_cost_model_prices_ring_gqa_repeat_and_ulysses_fallback():
    """The two real divergences the analyzer surfaced in this PR, now
    fixed in the cost model: (a) ring under a head-TP degree that does
    not divide the kv heads repeats kv up front, so the ppermute moves
    full-head bytes; (b) ulysses whose local heads don't split the seq
    degree falls back to the ring exchange — priced as ppermute, not
    all-to-all."""
    # (a) heads=6, kv=3, model=2: 3 % 2 != 0 -> repeat -> 6-head bytes
    graph, strategy, _ = _llama_sp_subject("ring", heads=6, kv_heads=3)
    axis_sizes = {"data": 2, "seq": 2, "model": 2}
    cm = _cost_model(axis_sizes)
    node = [n for n in graph.nodes if n.name == "l0_attn"][0]
    steps = cm.attention_comm_spec(graph, node, strategy["l0_attn"])
    pp = [st for st in steps if st.kind == "ppermute"]
    assert len(pp) == 1
    o = node.outputs[0]
    b, s, dt = o.dims[0].size, o.dims[1].size, o.dtype.size_bytes
    hd = node.attrs.kdim
    assert pp[0].nbytes == 2 * b * s * 6 * hd * dt  # repeated: 6 heads
    # (b) heads=4, model=2 -> 2 local heads; seq degree 4 won't divide
    graph, strategy, _ = _llama_sp_subject("ulysses", heads=4, kv_heads=2)
    axis_sizes = {"data": 1, "seq": 4, "model": 2}
    cm = _cost_model(axis_sizes)
    node = [n for n in graph.nodes if n.name == "l0_attn"][0]
    steps = cm.attention_comm_spec(graph, node, strategy["l0_attn"])
    kinds = {st.kind for st in steps if st.kind != "all_reduce"}
    assert kinds == {"ppermute"}, steps


# ---------------------------------------------------------------------------
# rulesat pass


def test_rulesat_corpus_all_fireable_and_agrees_with_soundness():
    """Acceptance: every rule the soundness suite can instantiate is
    classified fireable (no false 'inert' on a sound rule) — and the
    shipped corpus contains no unsatisfiable rule."""
    from flexflow_tpu.analysis.rulesat import classify_corpus
    from flexflow_tpu.search.soundness import instantiate_rule
    from flexflow_tpu.search.xfer_engine import (
        DEFAULT_RULES_PATH,
        find_matches,
    )

    with open(DEFAULT_RULES_PATH) as f:
        rules = json.load(f)
    cls = classify_corpus(rules)
    assert len(cls) == len(rules)
    unsat = [n for n, r in cls.items() if r["status"] != "fireable"]
    assert unsat == [], unsat
    # independent spot check against the soundness instantiation
    for rule in rules[:: max(1, len(rules) // 25)]:
        instantiable = any(
            (inst := instantiate_rule(rule, profile_nd=nd)) is not None
            and find_matches(rule, inst[0])
            for nd in (2, 3, 4)
        )
        if instantiable:
            assert cls[rule["name"]]["status"] == "fireable", rule["name"]


def test_rulesat_flags_unsatisfiable_rules():
    """Seeded defect 2 (ISSUE 3): guards that can never hold are
    classified inert_unsatisfiable with a reason naming the guard."""
    from flexflow_tpu.analysis.rulesat import classify_rule

    def lin_rule(when, name):
        return {
            "name": name,
            "src": {"nodes": [{"id": "l", "type": "LINEAR", "when": when}],
                    "inputs": [["x", "l", 0]], "outputs": [["l", 0]]},
            "dst": {"nodes": [{"id": "n", "type": "NOOP", "reuse": "l",
                               "name": "{l}", "attrs": {}}],
                    "inputs": [["x", "n", 0]], "outputs": [["n", 0]]},
        }

    rec = classify_rule(lin_rule({"attr_eq": ["bogus_field", 5]},
                                 "bad_attr_field"))
    assert rec["status"] == "inert_unsatisfiable"
    assert any("bogus_field" in r for r in rec["reasons"])

    rec = classify_rule(lin_rule({"definitely_unknown_pred": True},
                                 "bad_predicate"))
    assert rec["status"] == "inert_unsatisfiable"
    assert any("definitely_unknown_pred" in r for r in rec["reasons"])

    bad_kind = {
        "name": "bad_unary_kind",
        "src": {"nodes": [{"id": "u", "type": "ELEMENT_UNARY",
                           "when": {"unary_kind": ["frobnicate"]}}],
                "inputs": [["x", "u", 0]], "outputs": [["u", 0]]},
        "dst": {"nodes": [{"id": "n", "type": "NOOP", "reuse": "u",
                           "name": "{u}", "attrs": {}}],
                "inputs": [["x", "n", 0]], "outputs": [["n", 0]]},
    }
    rec = classify_rule(bad_kind)
    assert rec["status"] == "inert_unsatisfiable"
    assert any("frobnicate" in r for r in rec["reasons"])

    # a malformed guard must be CLASSIFIED, not crash the analyzer
    for bad_arg in ([], 5, {"f": 1}, ["only_field"]):
        rec = classify_rule(lin_rule({"attr_eq": bad_arg},
                                     "malformed_attr_eq"))
        assert rec["status"] == "inert_unsatisfiable", bad_arg
        assert any("malformed" in r for r in rec["reasons"]), bad_arg

    # the pass surfaces them as error findings
    from flexflow_tpu.analysis.rulesat import rulesat_pass

    ctx = AnalysisContext(rules=[lin_rule({"attr_eq": ["bogus_field", 5]},
                                          "bad_attr_field")])
    findings = rulesat_pass(ctx)
    assert any(f.code == "rule-unsatisfiable" and f.severity == "error"
               and f.where == "bad_attr_field" for f in findings)


def test_rulesat_classification_snapshot_committed():
    """docs/rule_coverage.json carries the per-rule classification (with
    reachability) next to the search-measured fires/profit sections."""
    with open(os.path.join(REPO, "docs", "rule_coverage.json")) as f:
        snap = json.load(f)
    cls = snap.get("classification", {})
    assert cls.get("rules"), "classification section missing — regenerate " \
        "with: python tools/fflint.py --passes rulesat --write-coverage"
    assert len(cls["rules"]) == snap["corpus_size"]
    for name, rec in cls["rules"].items():
        assert rec["status"] in ("fireable", "inert_unsatisfiable"), name
        assert rec["status"] == "fireable", f"{name} shipped unsatisfiable"
        # search-observed fires must be classified reachable
        if rec.get("snapshot_fired"):
            assert rec["baseline_reach"] == "fires_on_baselines", name
    assert "profit_by_config" in snap  # search-measured data preserved


# ---------------------------------------------------------------------------
# hostsync pass


def test_hostsync_flags_item_sync_in_decode_loop(tmp_path):
    """Seeded defect 3 (ISSUE 3): a per-token .item() sync in a decode
    loop is an error; the pragma suppresses an annotated line."""
    from flexflow_tpu.analysis.hostsync import scan_file

    bad = tmp_path / "decode.py"
    bad.write_text(textwrap.dedent("""\
        import jax.numpy as jnp

        def decode_loop(self, steps):
            while True:
                tok = self._step()
                t = tok.item()
                self.tokens.append(t)

        def annotated_loop(self):
            for x in self.batch:
                t = x.item()  # fflint: host-ok (singleton control read)
                self.use(t)

        def non_directive_comment(self):
            for x in self.batch:
                t = x.item()  # fflint: broken, fix this
                self.use(t)
    """))
    findings = scan_file(str(bad))
    errs = [f for f in findings if f.code == "item-sync-in-loop"]
    # the loose comment is NOT a directive — only host-ok/ignore suppress
    assert len(errs) == 2, findings
    assert all(f.severity == "error" for f in errs)
    assert {"decode.py:6", "decode.py:16"} == {f.where.split("/")[-1]
                                              for f in errs}
    assert all("per-element device sync" in f.message for f in errs)


def test_hostsync_flags_jnp_in_host_loop_and_shape_branch(tmp_path):
    from flexflow_tpu.analysis.hostsync import scan_file

    src = tmp_path / "hot.py"
    src.write_text(textwrap.dedent("""\
        import jax
        import jax.numpy as jnp

        def per_token_host_loop(tokens):
            out = []
            for t in tokens:
                out.append(jnp.exp(t))
            return out

        def step(x):
            if x.shape[0] > 4:
                return x * 2
            return x

        step = jax.jit(step)
    """))
    findings = scan_file(str(src))
    codes = {f.code for f in findings}
    assert "jnp-in-host-loop" in codes
    assert "shape-branch-in-jit" in codes
    assert all(f.severity == "warning" for f in findings)


def test_hostsync_repo_hot_paths_clean():
    """runtime/, serving.py, paged/, spec/ carry no unannotated host-sync
    hazards (intentional per-tick syncs are '# fflint: host-ok')."""
    from flexflow_tpu.analysis.hostsync import default_src_paths, scan_paths

    findings = scan_paths(default_src_paths())
    gating = [f for f in findings if f.severity in ("error", "warning")]
    assert gating == [], [(f.where, f.code) for f in gating]


def test_hostsync_gate_covers_prefix_cache_and_chunked_prefill():
    """The tier-1 hostsync gate (fflint --passes hostsync) actually scans
    the prefix-cache/chunked-prefill hot paths (ISSUE 5 satellite): the
    scheduler, pool, and executor files are inside default_src_paths and
    scan clean — the ragged-launch refactor centralized the per-tick
    host transfers in the straight-line `_launch` helper, so the tick
    loops themselves carry no per-token syncs (and need no pragmas)."""
    import os

    from flexflow_tpu.analysis.hostsync import default_src_paths, scan_file

    roots = default_src_paths()
    paged_root = [p for p in roots if p.endswith("paged")]
    runtime_root = [p for p in roots if p.endswith("runtime")]
    assert paged_root and runtime_root, roots
    sched = os.path.join(paged_root[0], "scheduler.py")
    pool = os.path.join(paged_root[0], "pool.py")
    execu = os.path.join(runtime_root[0], "executor.py")
    assert os.path.exists(sched) and os.path.exists(pool)
    for path in (sched, pool, execu):
        findings = scan_file(path)
        gating = [f for f in findings
                  if f.severity in ("error", "warning")]
        assert gating == [], [(f.where, f.code) for f in gating]
    # the per-tick transfers live in the shared packed-launch helper
    # (one descriptor transfer per launch, not per token) — the prefill
    # tick itself no longer hosts an in-loop sync to annotate
    with open(sched) as f:
        src = f.read()
    assert "def _prefill_tick" in src
    assert "def _launch" in src


def test_hostsync_gate_covers_obs_instrumentation():
    """The fftrace instrumentation (ISSUE 8 satellite) is inside the
    hostsync gate: obs/ is a default scan root, and the span recorder +
    the instrumented scheduler/spec tick bodies all scan clean — tracing
    must not introduce unannotated host syncs into the tick loop."""
    from flexflow_tpu.analysis.hostsync import (
        DEFAULT_ROOTS,
        default_src_paths,
        scan_paths,
    )

    assert "obs" in DEFAULT_ROOTS
    obs_root = [p for p in default_src_paths() if p.endswith("obs")]
    assert obs_root
    findings = scan_paths(obs_root)
    gating = [f for f in findings if f.severity in ("error", "warning")]
    assert gating == [], [(f.where, f.code) for f in gating]


# ---------------------------------------------------------------------------
# hostsync device-loop rule (ISSUE 11 satellite): while_loop/fori_loop/
# scan bodies must contain ZERO host syncs — no pragma escape hatch


def test_hostsync_device_loop_flags_syncs_in_loop_body(tmp_path):
    """A host sync inside a lax.while_loop body is an error, and the
    '# fflint: host-ok' pragma does NOT suppress it: a traced device
    loop cannot host-sync intentionally, so an annotation there is
    always wrong."""
    from flexflow_tpu.analysis.hostsync import scan_file

    bad = tmp_path / "mega.py"
    bad.write_text(textwrap.dedent("""\
        import jax
        import numpy as np

        def megastep(state0):
            def cond(state):
                t, done = state
                return (t < 8) & ~done.item()  # fflint: host-ok (nope)

            def body(state):
                t, done = state
                host = np.asarray(done)
                jax.device_get(done)
                return (t + 1, done)

            return jax.lax.while_loop(cond, body, state0)
    """))
    findings = scan_file(str(bad))
    dl = [f for f in findings if f.code == "device-loop"]
    assert len(dl) == 3, findings  # .item(), np.asarray, device_get
    assert all(f.severity == "error" for f in dl)
    # messages name the loop body: "in while_loop body 'cond': ..."
    assert {"cond", "body"} == {f.message.split("'")[1] for f in dl}


def test_hostsync_device_loop_clean_body_and_lambda(tmp_path):
    """Pure-jnp bodies scan clean; a lambda cond is resolved inline and
    flagged when it syncs."""
    from flexflow_tpu.analysis.hostsync import scan_file

    src = tmp_path / "loops.py"
    src.write_text(textwrap.dedent("""\
        import jax
        import jax.numpy as jnp

        def clean(state0):
            def body(state):
                t, x = state
                return (t + 1, jnp.exp(x))

            return jax.lax.while_loop(lambda s: s[0] < 4, body, state0)

        def lam(state0):
            return jax.lax.while_loop(
                lambda s: s[1].item() < 4, lambda s: s, state0)
    """))
    findings = [f for f in scan_file(str(src)) if f.code == "device-loop"]
    assert len(findings) == 1, findings
    assert "<lambda>" in findings[0].message


def test_hostsync_device_loop_gate_covers_the_routers_rounds():
    """A device loop the tree runs (the expert router's rounds of a
    maximum, ops/expert_share.py `_top_k`'s `lax.fori_loop`) is inside
    the device-loop gate AND scans clean. Pairing device_loop_bodies
    with scan_file makes the zero-findings half meaningful: the body was
    actually seen."""
    from flexflow_tpu.analysis.hostsync import (
        device_loop_bodies,
        scan_file,
    )

    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "flexflow_tpu", "ops", "expert_share.py")
    path = os.path.abspath(path)
    bodies = device_loop_bodies(path)
    assert {(b["kind"], b["body"]) for b in bodies} == {
        ("fori_loop", "round_")}, bodies
    findings = [f for f in scan_file(path) if f.code == "device-loop"]
    assert findings == [], [(f.where, f.message) for f in findings]


# ---------------------------------------------------------------------------
# hostsync stale-pragma hygiene (ISSUE 4 satellite)


def test_hostsync_flags_stale_pragma(tmp_path):
    """A '# fflint: host-ok' that suppresses a real finding is used; one
    annotating code that no longer trips any check is flagged info so
    annotations cannot rot into blanket noise."""
    from flexflow_tpu.analysis.hostsync import scan_file

    src = tmp_path / "mixed.py"
    src.write_text(textwrap.dedent("""\
        def used_pragma(self):
            for x in self.batch:
                t = x.item()  # fflint: host-ok (singleton control read)
                self.use(t)

        def stale_pragma(self):
            total = 0  # fflint: host-ok (nothing hazardous left here)
            return total

        def documented(self):
            "Annotate syncs with '# fflint: host-ok (reason)' comments."
            return 1
    """))
    findings = scan_file(str(src))
    stale = [f for f in findings if f.code == "stale-pragma"]
    # the docstring MENTIONING the directive is neither stale nor a
    # suppression — only real comment tokens count
    assert len(stale) == 1, findings
    assert stale[0].severity == "info"
    assert stale[0].where.endswith(":7")
    # the used pragma's suppression still works: no item-sync error
    assert not any(f.code == "item-sync-in-loop" for f in findings)


def test_hostsync_repo_has_no_stale_pragmas():
    from flexflow_tpu.analysis.hostsync import default_src_paths, scan_paths

    stale = [f for f in scan_paths(default_src_paths())
             if f.code == "stale-pragma"]
    assert stale == [], [(f.where, f.message) for f in stale]


# ---------------------------------------------------------------------------
# hloaudit pass (ISSUE 4 tentpole): ground-truth audit of lowered programs
# vs the search cost model


_SAMPLE_HLO = """\
HloModule jit_step

ENTRY %main {
  %ar = f32[4,128,64]{2,1,0} all-reduce(f32[4,128,64]{2,1,0} %x), replica_groups={{0,1},{2,3},{4,5},{6,7}}, metadata={op_name="jit(step)/jit(main)/jvp(l0_attn_7)/dot_general" source_file="a.py" source_line=1}
  %ag = f32[8,128,64]{2,1,0} all-gather(f32[4,128,64]{2,1,0} %y), replica_groups=[4,2]<=[8], dimensions={0}, metadata={op_name="jit(step)/jit(main)/transpose(jvp(l0_ff_9))/convert" source_file="a.py" source_line=2}
  %cp = u32[32768]{0} collective-permute(u32[32768]{0} %r), replica_groups={{0,1}}, metadata={op_name="jit(step)/jit(main)/jvp(l0_attn_7)/jit(_bernoulli)/jit(_uniform)/slice" source_file="a.py" source_line=3}
  %t = f32[8,128,64]{2,1,0} transpose(f32[8,64,128]{2,1,0} %z), dimensions={0,2,1}
  %c = f32[4,128,64]{2,1,0} copy(f32[4,128,64]{2,1,0} %w)
  ROOT %out = f32[] constant(0)
}
"""


def test_hloaudit_parser_attributes_and_classifies():
    """Collectives parse with payload bytes, replica-group sizes (both
    textual and iota formats), stable-key node attribution from metadata
    op_names (fwd jvp and bwd transpose paths), and partitioned-RNG
    plumbing marked so the diff skips it; transpose/copy totals match."""
    from flexflow_tpu.analysis.hloaudit import parse_hlo_module

    s = parse_hlo_module(_SAMPLE_HLO, ["l0_attn_7", "l0_ff_9"])
    by_kind = {c.kind: c for c in s.collectives}
    assert set(by_kind) == {"all-reduce", "all-gather",
                            "collective-permute"}
    ar = by_kind["all-reduce"]
    assert (ar.node, ar.group_size, ar.rng) == ("l0_attn_7", 2, False)
    assert ar.payload == 4 * 128 * 64 * 4
    ag = by_kind["all-gather"]
    assert (ag.node, ag.group_size) == ("l0_ff_9", 2)  # iota groups
    cp = by_kind["collective-permute"]
    assert cp.rng and cp.node == "l0_attn_7"
    assert s.transpose_bytes == 8 * 128 * 64 * 4
    assert s.copy_bytes == 4 * 128 * 64 * 4


_ASYNC_HLO = """\
HloModule jit_step

ENTRY %main {
  %ars = (f32[1024,256]{1,0}, f32[1024,256]{1,0}) all-reduce-start(f32[1024,256]{1,0} %x), replica_groups={{0,1}}, metadata={op_name="jit(step)/jvp(l0_ff_9)/add"}
  %ard = f32[1024,256]{1,0} all-reduce-done((f32[1024,256]{1,0}, f32[1024,256]{1,0}) %ars)
  %cps = (f32[1048576]{0}, u32[], u32[]) collective-permute-start(f32[1048576]{0} %y), replica_groups={{0,1}}, metadata={op_name="jit(step)/jvp(l0_attn_7)/slice"}
  %car = ((f32[256,64]{1,0}, f32[128]{0}), (f32[256,64]{1,0}, f32[128]{0})) all-reduce-start(f32[256,64]{1,0} %a, f32[128]{0} %b), replica_groups={{0,1}}, metadata={op_name="jit(step)/transpose(jvp(l0_moe_11))/add"}
  %var = (f32[512]{0}, f32[512]{0}, f32[256]{0}) all-reduce(f32[512]{0} %c, f32[512]{0} %d, f32[256]{0} %e), replica_groups={{0,1}}, metadata={op_name="jit(step)/jvp(l0_out_13)/add"}
  ROOT %out = f32[] constant(0)
}
"""


def test_hloaudit_parser_async_collectives():
    """TPU-style forms parse: async `-start` operand/result pair tuples
    halve (flat AND the nested combined-variadic form), array+scratch
    tuples sum, sync variadic (combined) tuples sum every member, and
    `-done` lines never double count."""
    from flexflow_tpu.analysis.hloaudit import parse_hlo_module

    s = parse_hlo_module(_ASYNC_HLO,
                         ["l0_ff_9", "l0_attn_7", "l0_moe_11", "l0_out_13"])
    assert len(s.collectives) == 4, s.collectives
    by = {c.node: c for c in s.collectives}
    # flat operand/result pair: halved
    assert by["l0_ff_9"].payload == 1024 * 256 * 4
    # array + u32[] scratch: summed (scratch is 8 noise bytes)
    assert by["l0_attn_7"].payload == 1048576 * 4 + 8
    # nested combined-variadic pair: halved to the two moved tensors
    assert by["l0_moe_11"].payload == (256 * 64 + 128) * 4
    # sync combined variadic: every member moves
    assert by["l0_out_13"].payload == (512 + 512 + 256) * 4


def test_transpose_audit_cli_is_a_wrapper():
    """One HLO parser in the tree: the tools CLI re-exports the pass's
    helpers instead of carrying its own drifted regexes."""
    import tools.hlo_transpose_audit as cli
    from flexflow_tpu.analysis import hloaudit

    assert cli.audit_hlo_text is hloaudit.audit_hlo_text
    assert cli.shape_bytes is hloaudit.shape_bytes
    offenders = cli.audit_hlo_text(_SAMPLE_HLO, min_bytes=1)
    assert [o["kind"] for o in offenders] == ["transpose", "copy"]


def test_priced_comm_manifest_structure():
    """The manifest exports kind/axes/bytes per stable node key: ring
    attention prices its ppermute, weight syncs appear as reduce events,
    and resharding edges carry src/dst keys."""
    graph, strategy, axis_sizes = _llama_sp_subject("ring")
    cm = _cost_model(axis_sizes)
    manifest = cm.priced_comm_manifest(graph, strategy, training=True)
    attn_key = next(n.stable_key() for n in graph.nodes
                    if n.name == "l0_attn")
    kinds = {e.kind for e in manifest["nodes"][attn_key]}
    assert "ppermute" in kinds
    assert "all_reduce" in kinds  # wo psum (+ bwd dx) + weight sync
    sources = {e.source for evs in manifest["nodes"].values()
               for e in evs}
    assert "weight_sync" in sources
    for e in manifest["edges"]:
        assert set(e) >= {"src", "dst", "kind", "nbytes"}
    # eval manifest carries no weight-sync traffic
    ev = cm.priced_comm_manifest(graph, strategy, training=False)
    assert not any(e.source == "weight_sync"
                   for evs in ev["nodes"].values() for e in evs)


def test_priced_manifest_mirrors_comm_event_pricing():
    """node_priced_events is the kind/byte decomposition of what
    node_comm_events actually prices: running each manifest event back
    through event_seconds must reproduce node_comm_events' per-node
    seconds on every BASELINE subject (attention and pipe-sharded nodes
    get structural checks instead — their seconds fold in compute
    overlap and hop latency the bytes manifest deliberately omits). A
    one-sided edit to either copy fails here instead of silently making
    the hloaudit manifest diverge from the search's pricing."""
    import math

    from flexflow_tpu.analysis.baselines import build_baseline_subjects
    from flexflow_tpu.parallel.comm_spec import axes_degree
    from flexflow_tpu.search.cost_model import CostModel, is_pipe_sharded
    from flexflow_tpu.search.machine_model import TPUMachineModel

    from flexflow_tpu.ffconst import OpType

    attention = (OpType.MULTIHEAD_ATTENTION, OpType.RING_ATTENTION)
    for name, graph, strategy, axis_sizes in build_baseline_subjects():
        ndev = 1
        for s in axis_sizes.values():
            ndev *= s
        cm = CostModel(TPUMachineModel.make("v5e", ndev), axis_sizes)
        for node in graph.topo_order():
            view = strategy.get(node.name, node.sharding)
            priced = [e for e in cm.node_priced_events(
                graph, node, view, training=True)
                if e.source == "node_comm"]
            comm = cm.node_comm_events(graph, node, view, training=True)
            where = f"{name}:{node.name}"
            if node.op_type in attention and any(
                    cm.attention_comm_spec(graph, node, view)):
                # attention seconds are compute-coupled (ring legs price
                # max(latency, transfer - overlapped compute) and may
                # drop entirely when hidden), so the mirror check is
                # structural: every axes comm prices must be in the
                # manifest, which may additionally carry hidden legs
                p_axes = [e.axes for e in priced]
                for axes, _t in comm:
                    assert tuple(axes) in p_axes, (where, axes, priced)
                assert len(priced) >= len(comm), (where, priced, comm)
                continue
            assert len(priced) == len(comm), (
                where, [(e.kind, e.axes) for e in priced],
                [a for a, _t in comm])
            if is_pipe_sharded(node, view):
                continue  # hop-latency folding differs by design
            t_priced = sum(cm.event_seconds(
                e.kind, e.nbytes, axes_degree(e.axes, cm.axis_sizes),
                e.axes) for e in priced)
            t_comm = sum(t for _a, t in comm)
            assert math.isclose(t_priced, t_comm, rel_tol=1e-9), (
                where, t_priced, t_comm)


@pytest.fixture(scope="module")
def audited_llama():
    """llama_tp_dp compiled end-to-end, eval_step AOT-lowered + XLA-
    compiled once, shared by the hloaudit tests (the expensive part;
    eval keeps the row-TP wo psum the fixtures need while lowering in a
    fraction of train_step's time — the full four-entry train audit runs
    in the slow-marked CLI acceptance test)."""
    from flexflow_tpu.analysis.baselines import build_baseline_executor
    from flexflow_tpu.analysis.hloaudit import (
        lower_executor_modules,
        parse_hlo_module,
    )

    executor, graph, strategy, axis_sizes = \
        build_baseline_executor("llama_tp_dp")
    cm = _cost_model(axis_sizes)
    mods = lower_executor_modules(executor, entries=["eval_step"],
                                  subject="llama_tp_dp")
    assert "hlo_text" in mods["eval_step"], mods["eval_step"]
    summary = parse_hlo_module(
        mods["eval_step"]["hlo_text"],
        [n.stable_key() for n in graph.nodes],
        memory=mods["eval_step"]["memory"])
    return executor, graph, strategy, axis_sizes, cm, mods, summary


def test_hloaudit_clean_on_llama_eval_step(audited_llama):
    """The real eval step audits clean against the (fixed) cost model —
    and the pass fills the per-entry program summary stats."""
    from flexflow_tpu.analysis import run_passes

    executor, graph, strategy, axis_sizes, cm, mods, _ = audited_llama
    ctx = AnalysisContext(graph=graph, strategy=strategy,
                          axis_sizes=axis_sizes, cost_model=cm,
                          subject="llama_tp_dp", hlo_modules=mods)
    report = run_passes(["hloaudit"], ctx)
    gating = [f for f in report.findings
              if f.severity in ("error", "warning")]
    assert gating == [], [(f.code, f.where, f.message) for f in gating]
    prog = ctx.hlo_summary["llama_tp_dp"]["eval_step"]
    assert prog["priced"] is True
    assert prog["collective_schedule"]["all-reduce"]["count"] > 0
    assert prog["attributed"] > 0
    assert prog["peak_bytes"] and prog["peak_bytes"] > 0


def test_hloaudit_flags_zeroed_priced_event(audited_llama):
    """Seeded divergence 1 (ISSUE 4): zero the priced all-reduce events
    of one attention node — the lowered module still runs that psum, so
    the diff must fail strict with the node and collective kind named."""
    from flexflow_tpu.analysis.hloaudit import diff_entry

    _, graph, strategy, _, cm, _, summary = audited_llama
    manifest = cm.priced_comm_manifest(graph, strategy, training=False)
    attn_key = next(n.stable_key() for n in graph.nodes
                    if n.name == "l0_attn")
    clean = diff_entry("llama_tp_dp", "eval_step", manifest, summary)
    assert [f for f in clean if f.severity == "error"] == []
    manifest["nodes"][attn_key] = [
        e for e in manifest["nodes"][attn_key] if e.kind != "all_reduce"
    ]
    flagged = [f for f in diff_entry("llama_tp_dp", "eval_step",
                                     manifest, summary)
               if f.code == "hlo-unpriced-collective"]
    assert flagged, "zeroed priced event not caught"
    assert flagged[0].severity == "error"
    assert attn_key in flagged[0].where
    assert "all-reduce" in flagged[0].message


def test_hloaudit_flags_hbm_over_budget(audited_llama):
    """Seeded divergence 2 (ISSUE 4): on a machine whose HBM the config
    exceeds, both the priced memory_per_chip and XLA's buffer-assignment
    peak must fail strict with the budget error."""
    from flexflow_tpu.analysis.hloaudit import check_memory
    from flexflow_tpu.search.cost_model import graph_cost
    from flexflow_tpu.search.machine_model import (
        TPUChipSpec,
        TPUMachineModel,
    )

    _, graph, strategy, _, cm, _, summary = audited_llama
    gc = graph_cost(graph, strategy, cm, training=True)
    tiny = TPUMachineModel(
        TPUChipSpec("tiny", 1e12, 1e6, 1e11, 5e10, 4, 2), 8)
    assert gc.memory_per_chip > tiny.memory_per_chip()
    flagged = check_memory("llama_tp_dp", "train_step",
                           gc.memory_per_chip, summary, tiny)
    budget = [f for f in flagged if f.code == "hlo-hbm-budget"]
    assert len(budget) == 2  # priced side AND lowered peak
    assert all(f.severity == "error" for f in budget)
    assert "llama_tp_dp:train_step" in budget[0].where
    # the real v5e budget is clean
    ok = check_memory("llama_tp_dp", "train_step", gc.memory_per_chip,
                      summary, cm.machine)
    assert [f for f in ok if f.code == "hlo-hbm-budget"] == []


def test_lowered_modules_entry_points(audited_llama):
    """lowered_modules exposes the four audited entry points for a
    decode-capable graph and rejects unknown names."""
    executor = audited_llama[0]
    assert executor.can_paged_decode()
    lows = executor.lowered_modules(["eval_step"])
    assert set(lows) == {"eval_step"}
    assert hasattr(lows["eval_step"], "compile")  # a jax Lowered
    with pytest.raises(ValueError) as ei:
        executor.lowered_modules(["decode_fn"])
    assert "paged_decode" in str(ei.value)


def test_lowered_paged_entries_are_the_ragged_step(audited_llama):
    """The two paged audit entries are two SHAPES of the one paged step
    program a server launches, ragged_step_fn(): lowered with the pools
    donated (every pool leaf carries an alias or donor mark in the
    module text), and Executor has no other paged step to lower."""
    executor = audited_llama[0]
    for dead in ("paged_decode_fn", "chunked_prefill_fn", "verify_fn"):
        assert not hasattr(executor, dead), dead
    lows = executor.lowered_modules(["paged_decode", "verify"],
                                    slots=2, max_nodes=4)
    assert set(lows) == {"paged_decode", "verify"}
    pools = executor.paged_kv_cache_specs(3, 16)
    leaves = sum(len(v) for v in pools.values())
    for name, window in (("paged_decode", 1), ("verify", 4)):
        text = lows[name].as_text()
        donated = (text.count("tf.aliasing_output")
                   + text.count("jax.buffer_donor"))
        assert donated == leaves, (name, donated, leaves)
        # (tables, pos, q_lens, depths, anc, ids) at this window
        assert f"tensor<2x{window}x{window}xi1>" in text, name
        assert f"tensor<2x{window}xi32>" in text, name


def test_sarif_serialization():
    """Finding -> SARIF: levels map (info -> note), hostsync file:line
    findings become physical locations, logical subjects survive."""
    from flexflow_tpu.analysis import Finding, Report
    from flexflow_tpu.analysis.sarif import report_to_sarif

    report = Report(findings=[
        Finding("hostsync", "error", "item-sync-in-loop",
                "serving.py:42", "sync in loop"),
        Finding("hloaudit", "info", "hlo-vanished-collective",
                "llama_tp_dp:train_step:l0_attn_7", "folded"),
    ])
    sarif = report_to_sarif(report)
    assert sarif["version"] == "2.1.0"
    run = sarif["runs"][0]
    assert {r["id"] for r in run["tool"]["driver"]["rules"]} == {
        "hostsync/item-sync-in-loop",
        "hloaudit/hlo-vanished-collective"}
    res = {r["ruleId"]: r for r in run["results"]}
    assert res["hostsync/item-sync-in-loop"]["level"] == "error"
    phys = res["hostsync/item-sync-in-loop"]["locations"][0][
        "physicalLocation"]
    assert phys["artifactLocation"]["uri"] == "serving.py"
    assert phys["region"]["startLine"] == 42
    note = res["hloaudit/hlo-vanished-collective"]
    assert note["level"] == "note"
    assert note["locations"][0]["logicalLocations"][0][
        "fullyQualifiedName"].startswith("llama_tp_dp:")


@pytest.mark.slow
def test_fflint_cli_hloaudit_strict_clean_on_all_baselines():
    """Acceptance: `fflint --passes hloaudit --strict` audits every
    BASELINE config's lowered entry points clean (the full run compiles
    ~30 XLA programs, so it is its own CI step, not part of tier-1)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "fflint.py"),
         "--passes", "hloaudit", "--strict", "--json"],
        capture_output=True, text=True, timeout=1800,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    payload = json.loads(proc.stdout)
    assert payload["counts"]["error"] == 0
    assert payload["counts"]["warning"] == 0
    programs = payload["stats"]["hloaudit"]["programs"]
    from flexflow_tpu.analysis.baselines import known_subject_names

    assert set(programs) == set(known_subject_names())
    for name in ("llama_tp_dp", "llama_sp_ring", "llama_sp_ulysses"):
        assert set(programs[name]) >= {"train_step", "eval_step",
                                       "paged_decode", "verify"}


# ---------------------------------------------------------------------------
# strategy-file import validation (model.py satellite)


def test_import_strategy_file_corrupt_fails_with_named_node(tmp_path):
    """A structurally-invalid view (an axis sharding two dims — GSPMD
    rejects it at lowering) fails import with the node named, instead of
    the cryptic XLA error it used to surface as."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.llama import LlamaConfig, build_llama
    from flexflow_tpu.parallel.sharding import ShardingView, view_to_json

    bad = {
        "l0_gate": view_to_json(ShardingView(
            ((("model",), (), ("model",)),))),
    }
    path = tmp_path / "strategy.json"
    path.write_text(json.dumps(bad))
    cfg = FFConfig(batch_size=8, mesh_shape={"data": 2, "model": 4})
    cfg.import_strategy_file = str(path)
    ff = FFModel(cfg)
    build_llama(ff, LlamaConfig.tiny(vocab=256), batch_size=8, seq_len=64)
    with pytest.raises(ValueError) as ei:
        ff.compile()
    assert "l0_gate" in str(ei.value)
    assert "duplicate-axis" in str(ei.value)


def test_import_strategy_file_stale_fails(tmp_path):
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.llama import LlamaConfig, build_llama
    from flexflow_tpu.parallel.sharding import ShardingView, view_to_json

    stale = {"renamed_node": view_to_json(
        ShardingView(((("data",), (), ()),)))}
    path = tmp_path / "stale.json"
    path.write_text(json.dumps(stale))
    cfg = FFConfig(batch_size=8, mesh_shape={"data": 2, "model": 4})
    cfg.import_strategy_file = str(path)
    ff = FFModel(cfg)
    build_llama(ff, LlamaConfig.tiny(vocab=256), batch_size=8, seq_len=64)
    with pytest.raises(ValueError) as ei:
        ff.compile()
    assert "renamed_node" in str(ei.value)


# ---------------------------------------------------------------------------
# CLI strict gate (the tier-1 acceptance bar: zero strict findings on all
# BASELINE configs + the shipped corpus + the serving/runtime sources)


def test_fflint_cli_strict_clean_on_baselines_and_corpus():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "fflint.py"),
         "--strict", "--json"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    payload = json.loads(proc.stdout)
    assert payload["counts"]["error"] == 0
    assert payload["counts"]["warning"] == 0
    # poolcheck rides the default gate: the model checker must have
    # fully explored both bounded configs (truncation would be a
    # warning and fail above)
    mc = payload["stats"]["poolcheck"]["model_check"]
    assert mc["explored_states"] > 1000
    assert set(mc["configs"]) == {"base", "spec", "tiered"}
    subjects = payload["stats"]["consistency"]["subjects"]
    for cfg_name in ("alexnet_cifar10", "resnet50", "bert_base",
                     "llama_tp_dp", "mixtral_ep", "inception_v3",
                     "llama_sp_ring", "llama_sp_ulysses"):
        assert cfg_name in subjects, subjects
    counts = payload["stats"]["rulesat"]["classification_counts"]
    assert counts.get("inert_unsatisfiable", 0) == 0
    assert counts.get("fires_on_baselines", 0) > 0
    assert sum(counts.values()) >= 400  # full corpus classified


def test_unknown_config_name_raises_instead_of_validating_nothing():
    """A typo'd --config must not silently check zero subjects and
    report a corrupt strategy file as clean."""
    from flexflow_tpu.analysis.baselines import build_baseline_subjects

    with pytest.raises(ValueError) as ei:
        build_baseline_subjects(["llama"])  # real name: llama_tp_dp
    assert "llama_tp_dp" in str(ei.value)


def test_fflint_cli_pass_selection_and_exit_codes(tmp_path):
    """--passes runs only the named pass; an error finding fails the run
    even without --strict."""
    bad = tmp_path / "loopy.py"
    bad.write_text("def f(xs):\n    for x in xs:\n        x.item()\n")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(f"""\
            import sys
            sys.path.insert(0, {REPO!r})
            from flexflow_tpu.analysis import AnalysisContext, run_passes
            report = run_passes(["hostsync"],
                                AnalysisContext(src_paths=[{str(bad)!r}]))
            sys.exit(1 if report.gating(strict=False) else 0)
        """)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# poolcheck: explicit-state model checking + aliasing lints for the
# paged serving state machine (ISSUE 9)


def test_poolcheck_registered_and_in_default_gate():
    assert "poolcheck" in available_passes()
    # the CLI default gate includes poolcheck (hloaudit stays opt-in)
    with open(os.path.join(REPO, "tools", "fflint.py")) as f:
        src = f.read()
    assert '"poolcheck"' in src.split("DEFAULT_PASSES")[1][:250]


def test_poolcheck_model_clean_and_fully_explored_on_real_pool():
    """The shipped PagePool + scheduler bookkeeping satisfy the whole
    invariant catalog over EVERY reachable state of both bounded
    scenarios — this is the executable spec future pool refactors
    (ragged kernel, KV tiering, quantized pages) must keep green."""
    from flexflow_tpu.analysis import poolcheck

    for config in ("base", "spec", "tiered"):
        res = poolcheck.model_check(config)
        assert res.hits == [], res.hits
        assert not res.truncated
        floor = {"base": 2000, "spec": 800, "tiered": 1500}[config]
        assert res.explored >= floor, (config, res.explored)


def test_poolcheck_flags_dropped_refcount_decrement_in_defrag():
    """Seeded defect 1: defrag() that corrupts a refcount (models a
    dropped decrement in the remap). The checker must name the broken
    invariant and hand back a minimal trace ending in the defrag op."""
    from flexflow_tpu.analysis import poolcheck
    from flexflow_tpu.paged.pool import PagePool

    class DroppedDecrementPool(PagePool):
        def defrag(self):
            perm, old_to_new = super().defrag()
            if self._refs:
                self._refs[sorted(self._refs)[0]] += 1
            return perm, old_to_new

    res = poolcheck.model_check("base", pool_factory=DroppedDecrementPool)
    names = {h[0] for h in res.hits}
    assert names & {"defrag-preserve", "refcount-owners"}, res.hits
    for name, _msg, trace in res.hits:
        assert trace[-1] == "defrag", trace
        replayed = poolcheck.replay(trace, "base",
                                    pool_factory=DroppedDecrementPool)
        assert any(v.split(":")[0] == name for v in replayed), (trace,
                                                               replayed)


def test_poolcheck_flags_cow_bypass_write_to_shared_tail():
    """Seeded defect 2: admission maps the shared donor tail page in
    place of the COW clone — the first write into it must trip the
    cow-write invariant (refcount!=1 / published rows overwritten)."""
    from flexflow_tpu.analysis import poolcheck

    res = poolcheck.model_check("base", mutations=("cow_bypass",))
    assert any(h[0] == "cow-write" for h in res.hits), res.hits
    name, msg, trace = next(h for h in res.hits if h[0] == "cow-write")
    assert "refcount" in msg or "partial tail" in msg or "full" in msg
    replayed = poolcheck.replay(trace, "base", mutations=("cow_bypass",))
    assert any(v.split(":")[0] == "cow-write" for v in replayed)


def test_poolcheck_flags_spec_scratch_registered_before_commit():
    """Seeded defect 3: speculative verify publishes its drafted tree
    page before the commit — uncommitted draft K/V reaches the hash
    index, which the spec-scratch invariant forbids."""
    from flexflow_tpu.analysis import poolcheck

    res = poolcheck.model_check("spec",
                                mutations=("scratch_preregister",))
    assert any(h[0] == "spec-scratch" for h in res.hits), res.hits
    _n, _m, trace = next(h for h in res.hits if h[0] == "spec-scratch")
    replayed = poolcheck.replay(trace, "spec",
                                mutations=("scratch_preregister",))
    assert any(v.split(":")[0] == "spec-scratch" for v in replayed)


@pytest.mark.parametrize("mutation", ["scale_cow_drop",
                                      "scale_realloc_leak",
                                      "scale_defrag_drop"])
def test_poolcheck_flags_dropped_scale_sidecar_rewrite(mutation):
    """Seeded defects 4-6: each way the quantized pool's scale sidecar
    can stop following its pages — the COW clone copying payload but
    not scale, an allocation leaking the previous tenant's scale, and a
    defrag that permutes payloads but leaves scales at the old slots —
    must trip the scale-sidecar invariant with a minimal replayable
    trace."""
    from flexflow_tpu.analysis import poolcheck

    res = poolcheck.model_check("base", mutations=(mutation,))
    assert any(h[0] == "scale-sidecar" for h in res.hits), (mutation,
                                                           res.hits)
    _n, msg, trace = next(h for h in res.hits
                          if h[0] == "scale-sidecar")
    assert "does not match its content state" in msg
    replayed = poolcheck.replay(trace, "base", mutations=(mutation,))
    assert any(v.split(":")[0] == "scale-sidecar" for v in replayed), \
        (trace, replayed)


def test_poolcheck_swap_op_models_the_drain_and_swap_handoff():
    """The `swap` op (strategy change in flight: publish tails, free
    leaf-first, requeue — the model of scheduler._detach_active feeding
    adopt_pool_from/absorb_requests) is part of the explored op set
    whenever a request is active, and the shipped hand-off replays
    clean — the exhaustive clean sweep above
    (test_poolcheck_model_clean_and_fully_explored_on_real_pool)
    already explores it from EVERY reachable state of both configs."""
    from flexflow_tpu.analysis import poolcheck

    for trace in (["admit(0)", "swap"],
                  ["admit(0)", "admit(1)", "step(0)", "swap",
                   "admit(0)", "swap"]):
        assert poolcheck.replay(trace, "base") == [], trace


def test_poolcheck_flags_swap_that_skips_freeing_detached_pages():
    """Seeded defect: a drain-and-swap that detaches live owners but
    leaves their pages allocated in the adopted pool — the carried
    requests re-admit and the old pages leak with no owner, which the
    refcount-owners invariant must catch with a minimal trace ending in
    the swap op."""
    from flexflow_tpu.analysis import poolcheck

    res = poolcheck.model_check("base", mutations=("swap_free_skip",))
    assert any(h[0] == "refcount-owners" for h in res.hits), res.hits
    name, _msg, trace = next(h for h in res.hits
                             if h[0] == "refcount-owners")
    assert trace[-1] == "swap", trace
    replayed = poolcheck.replay(trace, "base",
                                mutations=("swap_free_skip",))
    assert any(v.split(":")[0] == name for v in replayed), (trace,
                                                           replayed)


def test_poolcheck_tiered_reaches_spill_fetch_adopt():
    """The tiered config's new ops are all REACHABLE: BFS from the
    initial state enables spill (proactive spill_oldest), fetch
    (prefetch of a spilled hash), and adopt (the prefill->decode
    handoff through the tier) — plus alloc-pressure spills inside
    admit. A disabled op would make the clean sweep above vacuous for
    the tier."""
    from collections import deque

    from flexflow_tpu.analysis import poolcheck

    root = poolcheck.PoolModel(**poolcheck.CONFIGS["tiered"])
    assert root.tier is not None
    seen = {root.key()}
    frontier = deque([root])
    enabled = set()
    want = {"spill", "fetch", "adopt", "admit", "step"}
    while frontier and not want <= enabled:
        state = frontier.popleft()
        for label in state.enabled_ops():
            enabled.add(label.split("(")[0])
            child = state.clone()
            child.violations = []
            child.apply(label)
            k = child.key()
            if k not in seen:
                seen.add(k)
                frontier.append(child)
    assert want <= enabled, enabled
    # and a concrete spill -> handoff -> refetch walk replays clean on
    # the REAL pool: admit + finish parks pages dead-cached, spill
    # moves the oldest to the tier, the re-admission of the SAME
    # prefix transparently fetches it back
    trace = ["admit(0)", "step(0)", "step(0)", "step(0)", "step(0)",
             "spill", "admit(0)"]
    assert poolcheck.replay(trace, "tiered") == [], trace


def test_poolcheck_flags_spill_that_drops_the_scale_sidecar():
    """Seeded defect: the spill payload packs the page's rows but
    ZEROES its scale state — a fetch (possibly on another server's
    pool) would dequantize the int8 rows under the wrong scale. The
    tier-scales invariant must catch it at the spill itself with a
    minimal replayable counterexample."""
    from flexflow_tpu.analysis import poolcheck

    res = poolcheck.model_check("tiered",
                                mutations=("spill_scale_drop",))
    assert any(h[0] == "tier-scales" for h in res.hits), res.hits
    _n, msg, trace = next(h for h in res.hits if h[0] == "tier-scales")
    assert "does not match its content state" in msg
    # the defect fires the moment a page spills: the minimal trace ends
    # in one of the three spill-capable ops
    assert trace[-1] == "spill" or trace[-1].startswith(("adopt(",
                                                         "admit(",
                                                         "step(")), trace
    replayed = poolcheck.replay(trace, "tiered",
                                mutations=("spill_scale_drop",))
    assert any(v.split(":")[0] == "tier-scales" for v in replayed), \
        (trace, replayed)


def test_kv_pricing_dtype_misprice_fixture():
    """Seeded dtype mispricing: an int8 KV pool priced at the model
    dtype looks ~4x bigger than the buffers the executor actually
    allocates — the hloaudit priced-vs-lowered philosophy applied to
    the serving pool. The dtype-aware kv_cache_token_bytes must match
    the real int8+sidecar allocation EXACTLY (page_size chosen so the
    per-page scale bytes divide evenly), and the fp32 figure must show
    the >=3.5x misprice the kv_dtype parameter exists to fix."""
    from flexflow_tpu import FFConfig, FFModel, LossType
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.models.llama import LlamaConfig, build_llama
    from flexflow_tpu.paged.quant import resolve_kv_dtype
    from flexflow_tpu.search.cost_model import (kv_cache_elem_counts,
                                                kv_cache_token_bytes)

    ff = FFModel(FFConfig(batch_size=1))
    build_llama(ff, LlamaConfig.tiny(vocab=256), batch_size=1, seq_len=8,
                dtype=DataType.FLOAT)
    ff.compile(loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    num_pages, page_size = 6, 8  # 2*kv_heads*4 = 16 scale B % 8 == 0
    specs = ff.executor.paged_kv_cache_specs(
        num_pages, page_size, dtype=resolve_kv_dtype("int8"))
    actual = sum(s.size * s.dtype.itemsize
                 for bufs in specs.values() for s in bufs.values())
    actual_per_token = actual // (num_pages * page_size)

    priced_q = kv_cache_token_bytes(ff.graph, kv_dtype="int8",
                                    page_size=page_size)
    assert priced_q == actual_per_token, (priced_q, actual_per_token)
    # the misprice the fixture seeds: same pool billed at the model dtype
    priced_fp = kv_cache_token_bytes(ff.graph)
    assert priced_fp >= 3.5 * priced_q, (priced_fp, priced_q)
    # elem counts feed the servesearch pricer the same split
    elems, scale_elems = kv_cache_elem_counts(ff.graph)
    assert priced_q == elems + (scale_elems * 4) // page_size
    # a quantized dtype cannot be priced without the page amortizer
    with pytest.raises(ValueError, match="page_size"):
        kv_cache_token_bytes(ff.graph, kv_dtype="int8")


def test_poolcheck_pass_reports_findings_summary_and_traces(tmp_path):
    """Pass-function level: a seeded defect surfaces as an inv-* error
    Finding with the minimal counterexample in the message, the trace
    lands as a replayable JSON artifact, and the explored-state summary
    is filled for the CLI/CI."""
    from flexflow_tpu.analysis import poolcheck  # noqa: F401 (register)

    ctx = AnalysisContext(subject="pool",
                          poolcheck_mutations=["cow_bypass"],
                          poolcheck_trace_dir=str(tmp_path))
    report = run_passes(["poolcheck"], ctx)
    errs = [f for f in report.findings if f.severity == "error"]
    assert any(f.code == "inv-cow-write" for f in errs), report.findings
    f = next(f for f in errs if f.code == "inv-cow-write")
    assert f.where.startswith("poolcheck:model/")
    assert "Minimal counterexample" in f.message
    assert ctx.poolcheck_summary["explored_states"] > 0
    traces = list(tmp_path.glob("*inv-cow-write.json"))
    assert traces, list(tmp_path.iterdir())
    with open(traces[0]) as fh:
        blob = json.load(fh)
    from flexflow_tpu.analysis.poolcheck import replay

    replayed = replay(blob["trace"], blob["config"],
                      mutations=("cow_bypass",))
    assert any(v.split(":")[0] == blob["invariant"] for v in replayed)


def test_poolcheck_lint_flags_page_and_table_writes(tmp_path):
    """The static arm: .at[].set on a buffer outside the COW helper and
    a self._tables mutation outside the admission/defrag lifecycle are
    errors in state-machine files; cow-ok/table-ok pragmas suppress."""
    from flexflow_tpu.analysis import poolcheck

    bad = tmp_path / "scheduler.py"
    bad.write_text(textwrap.dedent("""\
        class S:
            def _admit(self, x):
                self._tables = x                       # allowlisted fn

            def _sneaky(self, i, v, row):
                self.kv = self.kv.at[i].set(v)
                self._tables[i] = row
                self.kv = self.kv.at[i].add(v)  # fflint: cow-ok (test)
    """))
    findings = poolcheck.lint_file(str(bad), rel="paged/scheduler.py")
    codes = [(f.code, f.where) for f in findings]
    assert ("page-write-outside-cow", "paged/scheduler.py:6") in codes
    assert ("table-write-outside-admission",
            "paged/scheduler.py:7") in codes
    # the allowlisted _admit write and the pragma'd .add are silent
    assert len([c for c, _ in codes
                if c != "stale-pragma"]) == 2, findings


def test_poolcheck_lint_ignores_kernel_files_and_flags_pool_privates(
        tmp_path):
    """.at[].set in a kernel/attention file is the normal functional
    write (not a state-machine hazard); pool._underscore access outside
    pool.py is a warning wherever it happens."""
    from flexflow_tpu.analysis import poolcheck

    kern = tmp_path / "attention.py"
    kern.write_text("def w(kv, i, v):\n    return kv.at[i].set(v)\n")
    assert poolcheck.lint_file(str(kern), rel="paged/attention.py") == []

    snoop = tmp_path / "metrics.py"
    snoop.write_text(textwrap.dedent("""\
        def scrape(self):
            return len(self.pool._refs)
    """))
    fs = poolcheck.lint_file(str(snoop), rel="obs/metrics.py")
    assert [f.code for f in fs] == ["pool-private-access"]
    assert fs[0].severity == "warning"


def test_poolcheck_lint_lock_discipline_and_pragmas(tmp_path):
    """A thread-owning server class whose public method reads a
    loop-mutated field without the lock is flagged; reads under
    `with self._lock` and def-line lock-ok pragmas are not; a pragma
    suppressing nothing is a stale-pragma info finding."""
    from flexflow_tpu.analysis import poolcheck

    srv = tmp_path / "server.py"
    srv.write_text(textwrap.dedent("""\
        import threading

        class Srv:
            def _start(self):
                self._thread = threading.Thread(target=self._loop)

            def _loop(self):
                self._steps = 1

            def racy(self):
                return self._steps

            def locked(self):
                with self._lock:
                    return self._steps

            def blessed(self):  # fflint: lock-ok (snapshot)
                return self._steps

        def free_fn():  # fflint: lock-ok (suppresses nothing)
            return 0
    """))
    fs = poolcheck.lint_file(str(srv), rel="spec/server.py")
    codes = [(f.code, f.where) for f in fs]
    assert ("unlocked-cross-thread-read", "spec/server.py:11") in codes
    assert len([c for c, _ in codes
                if c == "unlocked-cross-thread-read"]) == 1, fs
    assert ("stale-pragma", "spec/server.py:20") in codes


def test_poolcheck_repo_lint_clean_with_zero_suppression_debt():
    """The shipped serving sources pass the lint arm with no findings
    at all — including no stale pragmas, so every lock-ok/cow-ok in the
    tree is load-bearing (the ISSUE-9 hygiene-sweep bar)."""
    from flexflow_tpu.analysis import poolcheck

    fs = poolcheck.lint_paths(poolcheck.default_lint_paths())
    assert fs == [], [(f.code, f.where) for f in fs]


def test_fflint_since_mode_selects_passes_by_changed_roots():
    """--since maps diffs to the passes whose roots they touch; a
    docs-only diff selects nothing, a paged/ diff selects the serving
    lints but never hloaudit (opt-in only)."""
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(f"""\
            import sys
            sys.path.insert(0, {os.path.join(REPO, 'tools')!r})
            import importlib.util as u
            spec = u.spec_from_file_location(
                "ff_lint", {os.path.join(REPO, 'tools', 'fflint.py')!r})
            m = u.module_from_spec(spec)
            spec.loader.exec_module(m)
            sel = m.passes_for_changes
            cand = list(m.DEFAULT_PASSES) + ["hloaudit"]
            assert sel(["docs/serving.md"], cand) == []
            got = sel(["flexflow_tpu/paged/pool.py"], cand)
            assert "poolcheck" in got and "hostsync" in got, got
            assert "hloaudit" not in got, got
            assert "consistency" not in got, got
            got = sel(["flexflow_tpu/search/cost_model.py"], cand)
            assert "consistency" in got and "rulesat" in got, got
            assert m.changed_files("HEAD") == m.changed_files("HEAD")
            print("OK")
        """)],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK" in proc.stdout


# ---------------------------------------------------------------------------
# shapecheck: static launch-shape-space auditing + catalog soundness
# (ISSUE 14)


def test_shapecheck_registered_and_in_default_gate():
    assert "shapecheck" in available_passes()
    with open(os.path.join(REPO, "tools", "fflint.py")) as f:
        src = f.read()
    defaults = src.split("DEFAULT_PASSES")[1][:250]
    assert '"shapecheck"' in defaults
    # shapecheck joins the default gate WITHOUT displacing poolcheck
    assert '"poolcheck"' in defaults
    # --since selection knows shapecheck's source roots
    assert '"shapecheck":' in src.split("PASS_ROOTS")[1]


def test_shapecheck_window_cap_matches_scheduler():
    """The pass mirrors the scheduler's packed-window cap as a plain int
    (fflint must run on a bare checkout, so no serving import) — this is
    the tripwire that keeps the mirror honest when the cap moves."""
    from flexflow_tpu.analysis import shapecheck
    from flexflow_tpu.paged import scheduler

    assert shapecheck.PREFILL_WINDOW_ROWS == scheduler.PREFILL_WINDOW_ROWS


def test_shapecheck_flags_unclamped_window_with_taint_chain(tmp_path):
    """Seeded defect 1: a launch width flowing straight from
    len(prompt) — the compile-storm regression the pass exists to catch.
    The error names the taint chain line by line; the clamped variants
    (min cap, pow2 bucket) stay silent."""
    from flexflow_tpu.analysis import shapecheck

    bad = tmp_path / "scheduler.py"
    bad.write_text(textwrap.dedent("""\
        class S:
            def _tick(self, items, prompt, tr, ntr):
                take = len(prompt)
                window = take + 1
                self._launch(items, window, tr, ntr)

            def _clamped_tick(self, items, prompt, tr, ntr):
                window = min(len(prompt), self.prefill_chunk)
                self._launch(items, window, tr, ntr)

            def _bucketed_tick(self, items, prompt, tr, ntr):
                self._launch(items, self._bucket(len(prompt)), tr, ntr)
    """))
    findings = shapecheck.scan_file(str(bad), rel="paged/scheduler.py")
    errs = [f for f in findings if f.code == "shape-space-unbounded"]
    assert len(errs) == 1, [(f.code, f.where) for f in findings]
    err = errs[0]
    assert err.severity == "error"
    assert err.where == "paged/scheduler.py:5"
    # the taint chain walks source -> assignment -> launch, by line
    assert "line 3" in err.message and "len(prompt)" in err.message
    assert "line 4" in err.message and "line 5" in err.message
    # replay: the same scan on the same file reproduces the finding
    replayed = shapecheck.scan_file(str(bad), rel="paged/scheduler.py")
    assert [(f.code, f.where) for f in replayed] == \
        [(f.code, f.where) for f in findings]


def test_shapecheck_pragma_suppresses_and_stale_pragma_flagged(tmp_path):
    from flexflow_tpu.analysis import shapecheck

    src = tmp_path / "scheduler.py"
    src.write_text(textwrap.dedent("""\
        class S:
            def _tick(self, items, prompt, tr, ntr):
                w = len(prompt)
                self._launch(items, w, tr, ntr)  # fflint: shape-ok (test)

            def _quiet(self, items, tr, ntr):  # fflint: shape-ok (stale)
                self._launch(items, 8, tr, ntr)
    """))
    findings = shapecheck.scan_file(str(src), rel="paged/scheduler.py")
    codes = [(f.code, f.where) for f in findings]
    assert ("shape-space-unbounded", "paged/scheduler.py:4") not in codes
    assert codes == [("stale-pragma", "paged/scheduler.py:6")], findings


def test_shapecheck_repo_hot_paths_clean_and_entry_points_seen():
    """The shipped serving stack scans clean, and the jit inventory
    proves the scan actually saw launch machinery (a clean scan of zero
    entry points would prove nothing)."""
    from flexflow_tpu.analysis import shapecheck

    paths = shapecheck.default_src_paths()
    findings = shapecheck.scan_paths(paths)
    assert findings == [], [(f.code, f.where) for f in findings]
    execu = [p for p in paths if p.endswith("executor.py")][0]
    sites = shapecheck.jit_entry_points(execu)
    scopes = {s["scope"] for s in sites}
    assert {"ragged_step_fn", "paged_commit_fn"} <= scopes, scopes


def test_shapecheck_catalog_is_the_expected_closed_set():
    """slots=2 / prefill_chunk=6 paged catalog: the packed-prefill family
    plus the decode tick is exactly 12 ragged shapes ((2, 6) is a whole
    chunk with the other slot's decode row riding its launch), and the
    knobs land in the config echo warm_launch_shapes rebuilds launches
    from."""
    from flexflow_tpu.analysis.shapecheck import enumerate_catalog

    cat = enumerate_catalog(slots=2, max_len=32, page_size=4,
                            prefill_chunk=6)
    ragged = {tuple(s) for s in cat["entries"]["ragged_step"]["shapes"]}
    want = {(b, w) for w in range(1, 7) for b in (1, 2)}
    assert ragged == want, ragged
    assert cat["entries"]["pick_tokens"]["shapes"] == [[1], [2]]
    assert cat["total_compilations"] == 14
    assert cat["config"]["table_cols"] == 8      # ceil(32 / 4)
    assert cat["config"]["num_pages"] == 17      # slots*cols + null page

    # a spec tree wider than the prefill chunk adds its verify shapes
    # and the commit program; table slack covers the tree scratch rows
    spec = enumerate_catalog(slots=2, max_len=32, page_size=4,
                             prefill_chunk=6, spec_max_nodes=9,
                             spec_depth=2)
    ragged = {tuple(s) for s in spec["entries"]["ragged_step"]["shapes"]}
    assert ragged == want | {(1, 9), (2, 9)}, ragged
    assert spec["entries"]["paged_commit"]["shapes"] == [[2, 3]]
    assert spec["config"]["table_cols"] == 11    # ceil((32+9) / 4)

    # dense admission pads to pow2 buckets capped at max_len
    dense = enumerate_catalog(slots=2, max_len=32, paged=False)
    shapes = {tuple(s) for s in dense["entries"]["decode_step"]["shapes"]}
    assert shapes == {(2, 1), (1, 8), (1, 16), (1, 32)}, shapes


@pytest.mark.parametrize("server,total", [
    (dict(slots=8, prefill_chunk=64, max_len=4096, num_pages=704), 73),
    (dict(slots=8, prefill_chunk=256, max_len=12544, num_pages=3200), 97),
])
def test_shapecheck_catalog_of_the_benchmark_servers(server, total):
    """The launch-shape catalogs of the two benchmark server shapes
    (mistral-7b-serve1, mistral-small-4-serve1): what
    warm_launch_shapes() compiles and the benchmark reports as
    `launch_shapes`. Two of each total are the sampling program's."""
    from flexflow_tpu.analysis.shapecheck import enumerate_catalog

    cat = enumerate_catalog(paged=True, page_size=64, **server)
    assert cat["total_compilations"] == total
    assert set(cat["entries"]) == {"ragged_step", "pick_tokens"}
    assert cat["entries"]["ragged_step"]["count"] == total - 2
    assert "ragged_pack" not in cat["config"]


def test_shapecheck_pass_budget_and_summary():
    """The registered pass scans the repo clean, catalogs every default
    served config under stats, and warns (not errors) when a config's
    shape space exceeds the budget."""
    ctx = AnalysisContext(subject="shapes")
    report = run_passes(["shapecheck"], ctx)
    assert [f for f in report.findings if f.severity != "info"] == [], \
        [(f.code, f.where) for f in report.findings]
    assert ctx.shapecheck_summary is not None
    cats = ctx.shapecheck_summary["catalogs"]
    assert set(cats) == {"paged_base", "paged_spec", "dense"}
    for cat in cats.values():
        assert cat["total_compilations"] <= \
            ctx.shapecheck_summary["budget"]

    tight = AnalysisContext(subject="shapes", shapecheck_budget=3)
    tight_report = run_passes(["shapecheck"], tight)
    over = [f for f in tight_report.findings
            if f.code == "shape-space-over-budget"]
    assert len(over) == len(tight.shapecheck_summary["catalogs"])
    assert all(f.severity == "warning" for f in over)
    assert tight_report.gating(strict=True)
    assert not tight_report.gating(strict=False)


def test_shapecheck_shrunk_catalog_fails_soundness():
    """Seeded defect 2: deleting an enumerated shape from the catalog
    must turn a matching observed compile event into a
    shape-catalog-unsound error naming the witness — the gate that
    keeps the enumeration honest."""
    from flexflow_tpu.analysis.shapecheck import (
        check_soundness,
        enumerate_catalog,
    )

    cat = enumerate_catalog(slots=2, max_len=32, page_size=4,
                            prefill_chunk=6)
    events = [{"entry": "ragged_step", "shape": (2, 1), "seconds": 0.5,
               "steady_state": False},
              {"entry": "pick_tokens", "shape": (2,), "seconds": 0.1,
               "steady_state": False}]
    assert check_soundness(cat, events) == []

    shrunk = json.loads(json.dumps(cat))  # deep copy
    shrunk["entries"]["ragged_step"]["shapes"].remove([2, 1])
    findings = check_soundness(shrunk, events)
    assert [f.code for f in findings] == ["shape-catalog-unsound"]
    assert findings[0].severity == "error"
    assert findings[0].where == "shapecheck:catalog/ragged_step"
    assert "(2, 1)" in findings[0].message


def test_shapecheck_union_catalog_spans_a_strategy_swap():
    """union_catalogs merges per-strategy launch-shape catalogs into
    the one a drain-and-swap cutover is judged against: shapes from
    EITHER side are sound, shared shapes count once, and soundness
    still fails for a shape neither strategy enumerates."""
    from flexflow_tpu.analysis.shapecheck import (
        check_soundness,
        enumerate_catalog,
        union_catalogs,
    )

    old = enumerate_catalog(slots=2, max_len=32, page_size=4,
                            prefill_chunk=6)
    new = enumerate_catalog(slots=2, max_len=32, page_size=4,
                            prefill_chunk=4, spec_max_nodes=3,
                            spec_depth=2)
    union = union_catalogs(old, new)
    # entry-wise set union; the shared decode/pick shapes count once
    for cat in (old, new):
        for entry, ent in cat["entries"].items():
            got = {tuple(s) for s in union["entries"][entry]["shapes"]}
            assert got >= {tuple(s) for s in ent["shapes"]}, entry
    assert union["total_compilations"] < (old["total_compilations"]
                                          + new["total_compilations"])
    assert union["config"]["union"] == [old["config"], new["config"]]

    # the cutover gate: one event only the OLD side emits (a width-6
    # prefill), one only the NEW side emits (its verify's commit
    # program) — the union judges both sound
    events = [{"entry": "ragged_step", "shape": (1, 6), "seconds": 0.4,
               "steady_state": False},
              {"entry": "paged_commit", "shape": (2, 3), "seconds": 0.4,
               "steady_state": False}]
    assert check_soundness(old, [events[1]]) != []
    assert check_soundness(new, [events[0]]) != []
    assert check_soundness(union, events) == []
    rogue = [{"entry": "ragged_step", "shape": (2, 9), "seconds": 0.4,
              "steady_state": True}]
    assert [f.code for f in check_soundness(union, rogue)] == \
        ["shape-catalog-unsound"]


# ---------------------------------------------------------------------------
# racecheck: lock-discipline lint + bounded interleaving model checking
# over the threaded serving protocols (ISSUE 18)


def test_racecheck_registered_and_in_default_gate():
    assert "racecheck" in available_passes()
    # the CLI default gate includes racecheck (before poolcheck, which
    # delegates its lock lint to racecheck's inferred model)
    with open(os.path.join(REPO, "tools", "fflint.py")) as f:
        src = f.read()
    head = src.split("DEFAULT_PASSES")[1][:250]
    assert '"racecheck"' in head and '"poolcheck"' in head


def test_racecheck_lint_flags_dropped_lock_tier_mutation(tmp_path):
    """Seeded defect 1: a tier class whose spill loop writes _entries
    under the lock, while a public drop() mutates it lock-free — the
    field is inferred lock-guarded and the bare write is an error.
    Locked writes and inline race-ok pragmas are silent; a pragma
    suppressing nothing is stale."""
    from flexflow_tpu.analysis import racecheck

    bad = tmp_path / "tier.py"
    bad.write_text(textwrap.dedent("""\
        import threading

        class Tier:
            def start(self):
                self._spiller = threading.Thread(target=self._loop)

            def _loop(self):
                with self._lock:
                    self._entries["h"] = "payload"

            def drop(self, h):
                del self._entries[h]

            def locked_drop(self, h):
                with self._lock:
                    del self._entries[h]

            def relaxed(self, h):
                self._entries[h] = None  # fflint: race-ok (test relaxed)

        def free_fn():  # fflint: race-ok (suppresses nothing)
            return 0
    """))
    fs = racecheck.lint_file(str(bad), rel="disagg/host_tier.py")
    codes = [(f.code, f.where) for f in fs]
    assert ("race-unguarded-write", "disagg/host_tier.py:12") in codes
    err = next(f for f in fs if f.code == "race-unguarded-write")
    assert err.severity == "error"
    assert "_entries" in err.message and "_lock" in err.message
    assert ("stale-pragma", "disagg/host_tier.py:21") in codes
    # locked_drop and the pragma'd relaxed write are silent
    assert len(codes) == 2, fs


def test_racecheck_lint_flags_inverted_tier_scheduler_lock_order(
        tmp_path):
    """Seeded defect 2: spill holds the tier lock and calls into the
    scheduler (which takes its own lock) while evict holds the
    scheduler lock and calls back into the tier — a cross-thread
    deadlock cycle the one-level call-resolved order graph must name
    with both locks and a witness site per edge."""
    from flexflow_tpu.analysis import racecheck

    bad = tmp_path / "sched.py"
    bad.write_text(textwrap.dedent("""\
        import threading

        class HostTierX:
            def __init__(self):
                self._tier_lock = threading.Lock()

            def spill(self, sched):
                with self._tier_lock:
                    sched.admit_page()

        class SchedX:
            def __init__(self):
                self._sched_lock = threading.Lock()

            def admit_page(self):
                with self._sched_lock:
                    self.admitted = 1

            def evict(self, tier):
                with self._sched_lock:
                    tier.spill(self)
    """))
    fs = racecheck.lint_file(str(bad), rel="paged/scheduler.py")
    assert [f.code for f in fs] == ["lock-order-cycle"], fs
    f = fs[0]
    assert f.severity == "error"
    assert "HostTierX._tier_lock" in f.message
    assert "SchedX._sched_lock" in f.message
    assert "deadlock" in f.message


def test_racecheck_flags_handoff_double_submit_interleaving():
    """Seeded defect 3 (dynamic): a prefill worker that enqueues the
    same request twice hands two owners the same KV — the explorer
    finds the single-owner violation and the minimal trace replays to
    the same violation from the initial state."""
    from flexflow_tpu.analysis import racecheck

    def factory():
        return racecheck.HandoffModel(mutations=("double_submit",))

    res = racecheck.explore_interleavings(factory)
    assert any(h[0] == "single-owner" for h in res.hits), res.hits
    inv, msg, trace = next(h for h in res.hits
                           if h[0] == "single-owner")
    assert trace, "counterexample must carry a non-empty trace"
    # every step is a replayable 'tid:label' action
    assert all(":" in step for step in trace)
    replayed = racecheck.replay_interleaving(factory, trace)
    assert any(v.split(":")[0] == "single-owner" for v in replayed), \
        (trace, replayed)
    # the clean model explores the same space violation-free
    clean = racecheck.explore_interleavings(racecheck.HandoffModel)
    assert clean.hits == [] and not clean.truncated


def test_racecheck_pass_reports_findings_summary_and_traces(tmp_path):
    """Pass-function level: a seeded interleaving defect surfaces as an
    ilv-* error Finding with the minimal schedule in the message, the
    trace lands as a replayable JSON artifact, and the explored-state
    summary is filled for the CLI/CI."""
    from flexflow_tpu.analysis import racecheck

    ctx = AnalysisContext(subject="races",
                          racecheck_mutations=["unlocked_submit"],
                          racecheck_trace_dir=str(tmp_path))
    report = run_passes(["racecheck"], ctx)
    errs = [f for f in report.findings if f.severity == "error"]
    assert any(f.code == "ilv-future-dropped" for f in errs), \
        report.findings
    f = next(f for f in errs if f.code == "ilv-future-dropped")
    assert f.where == "racecheck:model/swap"
    assert "Minimal interleaving" in f.message
    assert ctx.racecheck_summary["explored"] > 0
    assert set(ctx.racecheck_summary["models"]) == \
        {"handoff", "tierpool", "swap", "launch_ahead"}
    traces = list(tmp_path.glob("interleave-swap-future-dropped.json"))
    assert traces, list(tmp_path.iterdir())
    with open(traces[0]) as fh:
        blob = json.load(fh)
    replayed = racecheck.replay_interleaving(
        lambda: racecheck.SwapModel(mutations=("unlocked_submit",)),
        blob["trace"])
    assert any(v.split(":")[0] == blob["invariant"] for v in replayed)


def test_racecheck_repo_lint_clean_with_zero_suppression_debt():
    """The shipped threaded serving sources pass the lint arm with no
    findings at all — no unguarded writes, no order cycles, no stale
    pragmas, so every race-ok in the tree is load-bearing (the ISSUE-18
    hygiene-sweep bar)."""
    from flexflow_tpu.analysis import racecheck

    fs = racecheck.lint_paths(racecheck.default_lint_paths())
    assert fs == [], [(f.code, f.where) for f in fs]


def test_fflint_since_selects_racecheck_and_demotes_to_lint_arm():
    """--since maps diffs touching the threaded serving roots (disagg/,
    obs/, serving.py) onto racecheck, and demotes it to lint-only so
    the pre-commit hook never pays for interleaving exploration."""
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(f"""\
            import importlib.util as u
            spec = u.spec_from_file_location(
                "ff_lint", {os.path.join(REPO, 'tools', 'fflint.py')!r})
            m = u.module_from_spec(spec)
            spec.loader.exec_module(m)
            sel = m.passes_for_changes
            cand = list(m.DEFAULT_PASSES)
            for path in ("flexflow_tpu/disagg/router.py",
                         "flexflow_tpu/obs/reqlog.py",
                         "flexflow_tpu/serving.py"):
                got = sel([path], cand)
                assert "racecheck" in got, (path, got)
            assert sel(["docs/serving.md"], cand) == []
            print("OK")
        """)],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK" in proc.stdout


# ---------------------------------------------------------------------------
# numcheck: dtype-flow & scale-discipline static analysis, the
# low-precision gate (ISSUE 19)


def test_numcheck_registered_and_in_default_gate():
    assert "numcheck" in available_passes()
    with open(os.path.join(REPO, "tools", "fflint.py")) as f:
        src = f.read()
    defaults = src.split("DEFAULT_PASSES")[1][:300]
    assert '"numcheck"' in defaults
    # numcheck joins the default gate WITHOUT displacing the others
    assert '"poolcheck"' in defaults and '"shapecheck"' in defaults
    # --since selection knows numcheck's source roots
    assert '"numcheck":' in src.split("PASS_ROOTS")[1]


def test_numcheck_flags_dropped_sidecar_read(tmp_path):
    """Seeded defect 1: a "k" payload read in a function with no trace
    of the scale sidecar — the compute-site extension of poolcheck's
    scale invariant. The paired variant (touches "_scale") and the
    metadata read (["k"].dtype) stay silent."""
    from flexflow_tpu.analysis import numcheck

    bad = tmp_path / "attention.py"
    bad.write_text(textwrap.dedent("""\
        class S:
            def _gather(self, bufs, tables):
                kg = bufs["k"][tables]
                return self._dense(kg)

            def _gather_paired(self, bufs, tables):
                kg = bufs["k"][tables] * bufs["k_scale"][tables]
                return self._dense(kg)

            def _dtype_name(self, bufs):
                return str(bufs["k"].dtype)
    """))
    findings = numcheck.scan_file(str(bad), rel="paged/attention.py")
    errs = [f for f in findings if f.code == "scale-unpaired-access"]
    assert [(f.severity, f.where) for f in errs] == \
        [("error", "paged/attention.py:3")], \
        [(f.code, f.where) for f in findings]
    assert "k_scale" in errs[0].message and "_gather" in errs[0].message


def test_numcheck_flags_f64_promotion_with_chain(tmp_path):
    """Seeded defect 2: a forced float64 in a decode-path fixture must
    produce dtype-silent-promotion carrying the derivation chain line
    by line, and the same scan replays to the same finding."""
    from flexflow_tpu.analysis import numcheck

    bad = tmp_path / "scheduler.py"
    bad.write_text(textwrap.dedent("""\
        class S:
            def _decode_tick(self, q, k, pos):
                posf = pos.astype(jnp.float64)
                scale = posf * 0.125
                logits = jnp.einsum("bhd,btd->bht", q, k)
                return logits * scale
    """))
    findings = numcheck.scan_file(str(bad), rel="paged/scheduler.py")
    errs = [f for f in findings if f.code == "dtype-silent-promotion"]
    assert len(errs) == 1, [(f.code, f.where) for f in findings]
    err = errs[0]
    assert err.severity == "error"
    assert err.where == "paged/scheduler.py:4"
    # the derivation chain walks creation -> use, by line
    assert "line 3" in err.message and "float64" in err.message
    assert "line 4" in err.message
    # replay: the same scan on the same file reproduces the finding
    replayed = numcheck.scan_file(str(bad), rel="paged/scheduler.py")
    assert [(f.code, f.where, f.message) for f in replayed] == \
        [(f.code, f.where, f.message) for f in findings]


def test_numcheck_flags_int8_payload_meeting_float_op(tmp_path):
    """int8 provenance reaching an einsum with no dequant on the path
    is the scale-less-garbage error; an explicit astype back to float
    (the dequant discipline) silences it."""
    from flexflow_tpu.analysis import numcheck

    bad = tmp_path / "attention.py"
    bad.write_text(textwrap.dedent("""\
        class S:
            def _bad(self, raw, q):
                qpool = raw.astype(jnp.int8)
                return jnp.einsum("btd,bsd->bts", q, qpool)

            def _ok(self, raw, q):
                qpool = raw.astype(jnp.int8)
                kg = qpool.astype(jnp.float32)
                return jnp.einsum("btd,bsd->bts", q, kg)
    """))
    findings = numcheck.scan_file(str(bad), rel="paged/attention.py")
    errs = [f for f in findings if f.code == "dtype-silent-promotion"]
    assert [(f.severity, f.where) for f in errs] == \
        [("error", "paged/attention.py:4")], \
        [(f.code, f.where) for f in findings]
    assert "int8" in errs[0].message and "line 3" in errs[0].message


def test_numcheck_accum_unspecified_and_cast_in_loop(tmp_path):
    """bf16 operands in a matmul without preferred_element_type warn
    (the accumulation dtype is XLA's choice); passing it silences the
    warning; an .astype inside a host loop is the info finding."""
    from flexflow_tpu.analysis import numcheck

    src = tmp_path / "mlp.py"
    src.write_text(textwrap.dedent("""\
        class S:
            def _mlp(self, x, w):
                xb = x.astype(jnp.bfloat16)
                return jnp.matmul(xb, w)

            def _mlp_pinned(self, x, w):
                xb = x.astype(jnp.bfloat16)
                return jnp.matmul(xb, w,
                                  preferred_element_type=jnp.float32)

            def _host(self, items, w):
                outs = []
                for it in items:
                    outs.append(it.astype(jnp.float32))
                return outs
    """))
    findings = numcheck.scan_file(str(src), rel="ops/mlp.py")
    codes = [(f.code, f.where) for f in findings]
    assert ("dtype-accum-unspecified", "ops/mlp.py:4") in codes, codes
    warn = [f for f in findings if f.code == "dtype-accum-unspecified"]
    assert len(warn) == 1 and warn[0].severity == "warning"
    assert "preferred_element_type" in warn[0].message
    info = [f for f in findings if f.code == "dtype-cast-in-loop"]
    assert [(f.severity, f.where) for f in info] == \
        [("info", "ops/mlp.py:14")], codes


def test_numcheck_pragma_suppresses_and_stale_flagged(tmp_path):
    from flexflow_tpu.analysis import numcheck

    src = tmp_path / "attention.py"
    src.write_text(textwrap.dedent("""\
        class S:
            def _gather(self, bufs, tables):
                kg = bufs["k"][tables]  # fflint: dtype-ok (fp pool)
                return self._dense(kg)

            def _quiet(self, x):  # fflint: dtype-ok (stale)
                return x + 1
    """))
    findings = numcheck.scan_file(str(src), rel="paged/attention.py")
    codes = [(f.code, f.where) for f in findings]
    assert ("scale-unpaired-access", "paged/attention.py:3") not in codes
    assert codes == [("stale-pragma", "paged/attention.py:6")], findings


def test_numcheck_repo_hot_paths_clean_and_sites_seen():
    """The shipped hot paths scan clean, and the site inventory proves
    the scan actually engaged them — payload reads in the layered
    decode cache and pool-introspection paths, accumulation ops in the
    kernels (a clean scan of zero sites would prove nothing)."""
    from flexflow_tpu.analysis import numcheck

    paths = numcheck.default_src_paths()
    findings = numcheck.scan_paths(paths)
    assert findings == [], [(f.code, f.where) for f in findings]
    base = os.path.join(REPO, "flexflow_tpu")
    att = numcheck.dtype_flow_sites(
        os.path.join(base, "paged", "attention.py"))
    kinds = {s["kind"] for s in att}
    assert "accum-op" in kinds, att
    ops = numcheck.dtype_flow_sites(
        os.path.join(base, "ops", "jax_ops.py"))
    scopes = {s["scope"] for s in ops if s["kind"] == "payload-read"}
    assert "_pipeline" in scopes, scopes
    sched = numcheck.dtype_flow_sites(
        os.path.join(base, "paged", "scheduler.py"))
    scopes = {s["scope"] for s in sched if s["kind"] == "payload-read"}
    assert "__init__" in scopes, scopes


def test_numcheck_hlo_arm_flags_downgrade_f64_and_unplanned_convert():
    """Seeded defect 3 (the HLO side): against a plan declaring f32
    accumulation and a {f32, bf16} dtype set, a module whose dots
    accumulate bf16 is hlo-accum-downgrade, an f64 instruction is
    hlo-unexpected-f64, and an f16 convert is hlo-unplanned-convert —
    each carrying the observed-vs-plan witness."""
    from flexflow_tpu.analysis import numcheck

    hlo = textwrap.dedent("""\
        HloModule jit_step
        fused {
          %p = bf16[8,16]{1,0} parameter(0)
          %q = bf16[16,4]{1,0} parameter(1)
          %d = bf16[8,4]{1,0} dot(%p, %q), lhs_contracting_dims={1}
          %w = f64[8,4]{1,0} convert(bf16[8,4]{1,0} %d)
          %h = f16[8,4]{1,0} convert(bf16[16,4]{1,0} %q)
        }
    """)
    num = numcheck.extract_numerics(hlo)
    assert num["dots"] == {"bf16": 1}
    assert num["f64_lines"] >= 1
    plan = {"compute": "f32", "accum": "f32", "kv": None,
            "allowed": ["bf16", "f32"], "allow_f64": False}
    findings = numcheck.diff_dtype_plan("subj", "train_step", plan, num)
    codes = {f.code: f for f in findings}
    assert set(codes) == {"hlo-accum-downgrade", "hlo-unexpected-f64",
                          "hlo-unplanned-convert"}, codes
    down = codes["hlo-accum-downgrade"]
    assert down.severity == "error" and down.where == "subj:train_step"
    assert "bf16" in down.message and "f32" in down.message
    assert codes["hlo-unexpected-f64"].severity == "error"
    assert codes["hlo-unplanned-convert"].severity == "warning"
    # the same module against a plan that DECLARES what it does is clean
    ok_plan = {"compute": "bf16", "accum": "bf16", "kv": None,
               "allowed": ["bf16", "f32", "f16"], "allow_f64": True}
    assert numcheck.diff_dtype_plan("subj", "train_step", ok_plan,
                                    num) == []


def test_numcheck_executor_dtype_plan_and_real_lowering():
    """Executor.dtype_plan() declares f32 accumulation, the weights'
    dtype an entry is lowered against (the f32 masters for train_step,
    the tree a server launches with for the paged entries: a llama's
    declared bf16), the pool payload dtype per paged entry (s8 + f32
    dequant targets for int8), and never f64 — and the llama baseline's REAL
    lowered paged_decode diffs clean against it while a zeroed
    (all-bf16) plan mutation makes the same module fail with
    hlo-accum-downgrade."""
    pytest.importorskip("jax")
    from flexflow_tpu.analysis import numcheck
    from flexflow_tpu.analysis.baselines import build_baseline_executor
    from flexflow_tpu.analysis.hloaudit import lower_executor_modules

    executor, _, _, _ = build_baseline_executor("llama_tp_dp")
    plan = executor.dtype_plan(kv_dtype="int8")
    pd = plan["paged_decode"]
    assert pd["compute"] == "bf16" and pd["accum"] == "f32"
    assert pd["kv"] == "s8" and not pd["allow_f64"]
    assert {"s8", "f32", "bf16"} <= set(pd["allowed"])
    assert plan["train_step"]["kv"] is None
    assert plan["train_step"]["compute"] == "f32"

    mods = lower_executor_modules(executor, entries=["paged_decode"],
                                  subject="llama_tp_dp")
    mod = mods["paged_decode"]
    assert "hlo_text" in mod, mod
    num = numcheck.extract_numerics(mod["hlo_text"])
    assert num["dots"], "no dots parsed from the lowered module"
    clean = numcheck.diff_dtype_plan(
        "llama_tp_dp", "paged_decode",
        executor.dtype_plan()["paged_decode"], num)
    assert clean == [], [(f.code, f.message[:90]) for f in clean]
    # mutation: a plan zeroed down to bf16 accumulation must reject the
    # very same f32-accumulating module the honest plan accepts...
    # nothing accumulates NARROWER than bf16 here, so flip the check:
    # claim f64 accumulation and the observed f32 dots are a downgrade
    wide = {"compute": "f64", "accum": "f64", "kv": None,
            "allowed": ["f64"], "allow_f64": True}
    flagged = numcheck.diff_dtype_plan("llama_tp_dp", "paged_decode",
                                       wide, num)
    assert any(f.code == "hlo-accum-downgrade" for f in flagged), \
        [(f.code, f.where) for f in flagged]


def test_numcheck_budget_arm_validates_catalog(monkeypatch):
    """The shipped catalog is healthy; a non-finite band and a deleted
    required band each become named budget findings."""
    from flexflow_tpu.analysis import num_budgets, numcheck

    assert num_budgets.validate_catalog() == {}
    assert numcheck.budget_findings() == []

    broken = dict(num_budgets.BUDGETS)
    broken["int8-kv-mixed-batch"] = num_budgets.Budget(
        float("nan"), "abs", ("tests",), "broken band")
    del broken["kv-canary-shadow-delta"]
    monkeypatch.setattr(num_budgets, "BUDGETS", broken)
    findings = numcheck.budget_findings()
    codes = {(f.code, f.where) for f in findings}
    assert ("budget-invalid",
            "analysis/num_budgets.py:int8-kv-mixed-batch") in codes
    assert ("budget-missing",
            "analysis/num_budgets.py:kv-canary-shadow-delta") in codes
    assert all(f.severity == "error" for f in findings)


def test_numcheck_pass_summary_and_since_selection(tmp_path):
    """The registered pass fills the scan inventory (files seen, site
    counts, budget count), and --since maps hot-path diffs onto
    numcheck."""
    ctx = AnalysisContext(subject="numerics")
    report = run_passes(["numcheck"], ctx, Report())
    assert report.findings == [], \
        [(f.code, f.where) for f in report.findings]
    s = ctx.numcheck_summary
    assert s["files_scanned"] > 10
    assert s["sites"]["accum-op"] > 0
    assert s["sites"]["payload-read"] > 0
    assert s["budgets"] >= 8

    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import importlib.util as u

        spec = u.spec_from_file_location(
            "ff_lint_nc", os.path.join(REPO, "tools", "fflint.py"))
        m = u.module_from_spec(spec)
        spec.loader.exec_module(m)
        cand = list(m.DEFAULT_PASSES)
        for path in ("flexflow_tpu/paged/quant.py",
                     "flexflow_tpu/ops/jax_ops.py",
                     "flexflow_tpu/runtime/executor.py"):
            assert "numcheck" in m.passes_for_changes([path], cand), path
        assert m.passes_for_changes(["docs/paged.md"], cand) == []
    finally:
        sys.path.remove(os.path.join(REPO, "tools"))
