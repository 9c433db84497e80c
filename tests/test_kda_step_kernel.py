"""``ops/pallas/kda_step.py::kda_step_stacked`` in interpret mode against
``ops/kda.py::kda_step``, the recurrence written out that it stands in for
on a TPU: the layer's row first, in the middle and last of the stacked leaf,
a block of all the heads, of 8 and of the rule's own. The read-out and the
written row agree to float32 rounding of a 128-term sum (``u`` feeds the
state, so the state is not bit-equal as ``ssm_step``'s is), every other row
of the leaf and every slot with ``g = 0, b = 0`` come back bit for bit, the
leaf stays float32, and eight steps through the kernel are ``kda_scan`` over
the same eight rows. The compiled kernel at the cell's widths is
``tests/test_chip_compile.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from picotron_tpu.ops import kda
from picotron_tpu.ops.pallas import kda_step as kernel

F32 = jnp.float32
ROWS, SLOTS, HEADS, KEYS, VALUES = 3, 3, 32, 128, 128
# heads a block: all of them, 8, the rule's own (16: 1 MiB of 64 KB heads)
BLOCKS = {"all": HEADS, "eight": 8, "rule": None}
PARKED = 1


def l2(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def operands(seed=0, parked=(PARKED,), steps=1):
    """A step's operands at toy widths from a state that is not zero, ``g``
    down to -8, ``b`` up to 2, ``parked`` slots at ``g = 0, b = 0``: (q, k,
    v, g, b, leaf)."""
    ks = jax.random.split(jax.random.key(seed), 6)
    qk = (SLOTS, steps, HEADS, KEYS)
    q = l2(jax.random.normal(ks[0], qk, F32)).astype(jnp.bfloat16)
    k = l2(jax.random.normal(ks[1], qk, F32)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (SLOTS, steps, HEADS, VALUES), jnp.bfloat16)
    g = -8.0 * jax.random.uniform(ks[3], qk, F32) ** 4
    b = 2.0 * jax.random.uniform(ks[4], (SLOTS, steps, HEADS), F32)
    where = jnp.asarray(parked, jnp.int32)
    g, b = g.at[where].set(0.0), b.at[where].set(0.0)
    leaf = jax.random.normal(
        ks[5], (ROWS, SLOTS, HEADS, KEYS, VALUES), F32)
    return q, k, v, g, b, leaf


def stacked(block, *args):
    return kernel.kda_step_stacked(*args, block_heads=BLOCKS[block],
                                   interpret=True)


def bit_equal(a, b):
    return np.array_equal(np.asarray(a).view(np.uint32),
                          np.asarray(b).view(np.uint32))


def close(got, want, part=1e-5):
    """Within ``part`` of the largest |value| wanted."""
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, rtol=0, atol=part * scale)


@pytest.mark.parametrize("row", range(ROWS))
@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_kernel_is_kda_step_on_the_row_and_leaves_the_rest(block, row):
    q, k, v, g, b, leaf = operands()
    o, out = stacked(block, q, k, v, g, b, leaf, jnp.int32(row))
    o_ref, state_ref = kda.kda_step(q, k, v, g, b, leaf[row])
    assert o.shape == o_ref.shape and o.dtype == F32
    assert out.shape == leaf.shape and out.dtype == F32
    close(out[row], state_ref)
    close(o, o_ref)
    for other in range(ROWS):
        if other != row:
            assert bit_equal(out[other], leaf[other]), other
    # the parked slot: exp(0) S + k 0, bit for bit
    assert bit_equal(out[row, PARKED], leaf[row, PARKED])
    assert not bit_equal(out[row, 0], leaf[row, 0])


@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_the_state_stays_float32_through_the_kernel(block):
    """What tier-1 holds of the dtype (the cell's ``correct`` cannot tell a
    bfloat16 state): a float32 leaf out whose advanced entries are not
    bfloat16 values, and a bfloat16 leaf refused by name."""
    q, k, v, g, b, leaf = operands(seed=3)
    _, out = stacked(block, q, k, v, g, b, leaf, 1)
    assert out.dtype == F32
    live = np.asarray(out[1, 0])
    rounded = np.asarray(out[1, 0].astype(jnp.bfloat16).astype(F32))
    assert np.mean(live != rounded) > 0.9
    with pytest.raises(ValueError, match="bfloat16"):
        stacked(block, q, k, v, g, b, leaf.astype(jnp.bfloat16), 1)


@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_eight_steps_through_the_kernel_are_the_scan(block):
    """Under ``jax.jit``, the row traced, as the engine runs it: eight
    steps one after another against ``kda_scan`` over the same eight rows,
    from the state the row holds."""
    q, k, v, g, b, leaf = operands(seed=5, steps=8)
    row = 2

    @jax.jit
    def eight(leaf, row):
        def body(leaf, x):
            o, leaf = stacked(block, *(a[:, None] for a in x), leaf, row)
            return leaf, o[:, 0]

        return lax.scan(body, leaf, tuple(
            jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, b)))

    out, o = eight(leaf, jnp.int32(row))
    o_ref, state_ref = kda.kda_scan(q, k, v, g, b, leaf[row])
    # eight roundings on top of one another, and the scan sums in matmuls
    close(jnp.moveaxis(o, 0, 1), o_ref, 1e-4)
    close(out[row], state_ref, 1e-4)
    assert bit_equal(out[row, PARKED], leaf[row, PARKED])
    assert bit_equal(out[:row], leaf[:row])


def test_off_a_tpu_the_row_form_is_the_step_written_out():
    """``kda_step(row=)`` off a TPU: the slice, the step and the update the
    model ran before the kernel, bit for bit."""
    q, k, v, g, b, leaf = operands()
    o, out = kda.kda_step(q, k, v, g, b, leaf, jnp.int32(1))
    o_ref, state_ref = kda.kda_step(q, k, v, g, b, leaf[1])
    assert bit_equal(o, o_ref) and bit_equal(out[1], state_ref)
    assert bit_equal(out[0], leaf[0]) and bit_equal(out[2], leaf[2])


def test_on_a_tpu_the_row_form_is_the_kernel(monkeypatch):
    """No switch: where ``on_tpu()`` says so, ``kda_step(row=)`` hands the
    leaf and the row to the kernel as they are."""
    seen = []

    def kernel_seen(*args):
        seen.append(args)
        return "o", "leaf"

    monkeypatch.setattr(kda, "on_tpu", lambda: True)
    monkeypatch.setattr(kernel, "kda_step_stacked", kernel_seen)
    q, k, v, g, b, leaf = operands()
    assert kda.kda_step(q, k, v, g, b, leaf, 2) == ("o", "leaf")
    assert len(seen) == 1 and seen[0][5] is leaf and seen[0][6] == 2
    # without a row it is the step written out, on a TPU too
    o, state = kda.kda_step(q, k, v, g, b, leaf[0])
    assert len(seen) == 1 and state.shape == leaf.shape[1:]


def _rows_twice(ops):
    return {n: jnp.concatenate([a] * 2, axis=1) if n in "qkvgb" else a
            for n, a in ops.items()}


# what is wrong -> (the operands made so, heads a block, the error names)
REFUSED = {
    "two_rows_a_sequence": (_rows_twice, None, "one-row step"),
    "a_bfloat16_leaf": (
        lambda ops: {**ops, "leaf": ops["leaf"].astype(jnp.bfloat16)},
        None, "bfloat16"),
    "a_leaf_of_other_slots": (
        lambda ops: {**ops, "leaf": ops["leaf"][:, :SLOTS - 1]},
        None, "state leaf"),
    "a_leaf_of_other_heads": (
        lambda ops: {**ops, "leaf": ops["leaf"][:, :, :HEADS // 2]},
        None, "state leaf"),
    "a_block_that_does_not_divide": (lambda ops: ops, 12,
                                     "blocks of 12 heads"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_kernel_refuses_what_it_cannot_run(what):
    change, block, match = REFUSED[what]
    ops = change(dict(zip(("q", "k", "v", "g", "b", "leaf"), operands())))
    with pytest.raises(ValueError, match=match):
        kernel.kda_step_stacked(*ops.values(), 0, block_heads=block,
                                interpret=True)
