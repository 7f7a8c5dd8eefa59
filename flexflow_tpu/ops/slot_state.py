"""What the lowerings of the STATE ops share (`runtime/executor.py`
`STATE_OPS`: the delta-rule layer of ops/kda_attention.py and the
state-space layer of ops/mamba2.py): how a ragged launch's items continue
their slots' states, read from the launch's own arrays.

A launch's B items of W rows are pieces of requests; item i continues
slot `state_slots[i]`'s state from row `pos[i]` for `q_lens[i]` rows.
What a lowering is promised: the items of one slot are CONSECUTIVE and in
row order. `item_chain` derives the rest: a run's first item reads the
slot's stored state, or starts from zero where it is a request's row 0;
the following items take the state the item before left; the run's last
item stores it (`store`). `conv_history` does the same for the rows a
causal convolution keeps of the past, without a loop over the items.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def item_chain(slots, pos, q_lens):
    """(slot (B,), start, fresh, last (B,) bool) of a launch's items: an
    item without rows takes the slot of the live item before it (the
    first live item's, before any), so that a slot's items are ONE run;
    `start` marks a run's first item, `last` its last, `fresh` a live
    item at row 0 of its request."""
    B = slots.shape[0]
    live = q_lens > 0
    idx = jnp.arange(B, dtype=jnp.int32)
    before = lax.cummax(jnp.where(live, idx, -1))
    src = jnp.where(before >= 0, before, jnp.argmax(live).astype(jnp.int32))
    slot = slots[src]
    change = slot[1:] != slot[:-1]
    edge = jnp.ones((1,), jnp.bool_)
    return (slot, jnp.concatenate([edge, change]), live & (pos == 0),
            jnp.concatenate([change, edge]))


def store(state, slot, last, values):
    """`values[i]` into `state[slot[i]]` where item i ends its run."""
    return state.at[jnp.where(last, slot, state.shape[0])].set(
        values.astype(state.dtype), mode="drop")


def conv_history(attrs, pre, q_lens, chain, conv_state):
    """The rows before each item, (B, taps - 1, 3c), and the conv state
    after the launch, without a loop over the items. A run's rows form
    one STREAM: the slot's stored rows (zeros where the run starts a
    request), then the live rows of its items in order. The rows before
    an item are the stream's `keep` rows that end where the item begins;
    what the run's last item stores are the `keep` rows that end where
    it ends. Live rows are found in the launch's rows packed to the
    front (`packed`), stored rows in `conv_state`."""
    slot, start, fresh, last = chain
    B, W, C = pre.shape
    keep = attrs.conv_taps - 1
    idx = jnp.arange(B, dtype=jnp.int32)
    begins = jnp.cumsum(q_lens) - q_lens           # live rows before item i
    first = lax.cummax(jnp.where(start, idx, 0))   # the run's first item
    in_run = begins - begins[first]                # ... of its own run
    # a request's row 0 is its run's first live row
    zeroed = lax.cummax(jnp.where(fresh, idx, -1)) >= first
    live = jnp.arange(W, dtype=jnp.int32)[None, :] < q_lens[:, None]
    packed = jnp.zeros((B * W, C), pre.dtype).at[
        jnp.where(live, begins[:, None] + jnp.arange(W), B * W)
    ].set(pre, mode="drop")

    def rows_ending_at(end_in_run, end):
        """(B, keep, C): stream rows [end - keep, end) of each item's
        run, `end` counted in live rows of the launch, `end_in_run` of
        the run."""
        back = jnp.arange(keep, dtype=jnp.int32)[None, :] - keep  # -keep..-1
        stored_at = end_in_run[:, None] + back + keep   # < keep: a stored row
        from_state = conv_state[slot[:, None],
                                jnp.clip(stored_at, 0, keep - 1)]
        from_launch = packed[jnp.clip(end[:, None] + back, 0, B * W - 1)]
        stored = stored_at < keep
        return jnp.where(
            stored[..., None],
            jnp.where(zeroed[:, None, None], 0, from_state), from_launch)

    hist = rows_ending_at(in_run, begins)
    after = rows_ending_at(in_run + q_lens, begins + q_lens)
    return hist, store(conv_state, slot, last, after)
