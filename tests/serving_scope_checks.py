"""What the serving step's name stacks must say, checked on a tiny graph's
ragged step lowered (and compiled, never run) on the CPU: the checks that
tests/test_launch_packed.py (five graph kinds) and tests/test_granite4h.py
(the sixth) run on their own module-scoped graphs at a decode and a chunk
launch. obs/scopes.py `classify_serving` is the reader under test."""

import contextlib
import re

import jax
import jax.numpy as jnp

from flexflow_tpu.obs import scopes
from flexflow_tpu.runtime.executor import launch_columns

PAGE, SLOTS, COLS = 8, 3, 4
# (items, window): a decode launch and a chunk launch of 8-row pieces
LAUNCHES = {"decode": (SLOTS, 1), "chunk": (4, 8)}
# the opcode follows the result type, which ends `]`, `}` or `)`
_OPCODE = re.compile(r"[\]})] ([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
HEAVY = ("dot", "convolution", "scatter", "gather", "custom-call")
NEW_SCOPES = scopes.ATTN_PARTS + (scopes.UNPACK,)


def lower_step(ff, launch):
    """The ragged step in the server's form (one packed descriptor, ids
    fed from the device) at one launch shape, on abstract arguments."""
    B, W = LAUNCHES[launch]
    ex = ff.executor
    classes = 1 if ex.page_classes() is None else 2
    pages = 1 + SLOTS * COLS
    kw = {"num_pages_window": pages} if classes == 2 else {}
    caches = jax.eval_shape(
        lambda: ex.init_paged_kv_cache(pages, PAGE, slots=SLOTS, **kw))
    _, width = launch_columns(W, classes, table_cols=COLS)
    sds = jax.ShapeDtypeStruct
    tr, ntr = ff.serving_params()
    return ex.ragged_step_fn().lower(
        tr, ntr, caches, None, None, None, sds((B, W), jnp.int32),
        sds((B, W, W), jnp.bool_), packed=sds((B, width), jnp.int32),
        feed=(None, sds((SLOTS,), jnp.int32)))


def check_every_heavy_instruction_has_a_group(ff, launch):
    """Every `dot`, `convolution`, `scatter`, `gather` and custom call of
    the compiled step classifies to a group, to its node's own group, and
    inside an attention node to one of the four parts; the step's own
    work reads under `unpack`."""
    groups = ff.executor.node_groups()
    assert set(groups.values()) <= set(scopes.GROUPS)
    txt = lower_step(ff, launch).compile().as_text()
    seen, parts, checked = set(), set(), 0
    for line in txt.splitlines():
        op, name = _OPCODE.search(line), _OP_NAME.search(line)
        if not (op and name):
            continue
        group, node, part = scopes.classify_serving(name.group(1))
        seen.add((group, node))
        if op.group(1) not in HEAVY:
            continue
        checked += 1
        assert group in scopes.GROUPS, line[:300]
        if node != scopes.UNPACK:
            assert groups[node] == group, line[:300]
        if group == scopes.ATTN:
            assert part in scopes.ATTN_PARTS, line[:300]
            parts.add(part)
        else:
            assert part is None
    assert checked > 10
    assert (scopes.GLUE, scopes.UNPACK) in seen
    # every node that does anything on the device is there under its group
    assert {g for g, _n in seen if g} >= set(groups.values()) - {None}
    assert parts == set(scopes.ATTN_PARTS)
    return seen


def check_scopes_change_nothing_but_names(ff, launch, monkeypatch):
    """With the scopes the serving step adds (group, part, `unpack`)
    patched to do nothing, the lowered module is the same text once
    locations are stripped: they are compile-time metadata, and nothing
    runs on a launch that did not before."""
    ex = ff.executor
    named = lower_step(ff, launch).as_text()
    real = jax.named_scope

    def quiet(name):
        if name in NEW_SCOPES:
            return contextlib.nullcontext()
        group, _, key = name.partition("/")
        return real(key if key and group in scopes.GROUPS else name)

    monkeypatch.setattr(jax, "named_scope", quiet)
    # a fresh jit of a fresh closure: nothing traced above is reused
    monkeypatch.setattr(ex, "_ragged_step_fn", None)
    low = lower_step(ff, launch)
    assert low.as_text() == named
    stacks = set(_OP_NAME.findall(low.compile().as_text()))
    assert stacks and not any(
        scopes.classify_serving(s)[0] for s in stacks), sorted(stacks)[:5]

