"""``ops/pallas/ssm_step.py::ssm_step_stacked`` in interpret mode against
``ops/ssm.py::ssm_step``, the elementwise step it stands in for on a TPU: the
three forms of ``B``/``C`` at toy widths (one row for all heads; a row a
group of neighbouring heads; a head's own), the layer's row first, in the
middle and last of the stacked leaf. The read-out and the written row agree
to float32 rounding of a ``d_state``-term sum, every other row of the leaf
and every slot with ``dt = 0`` come back bit for bit, and the same holds
under ``jax.jit`` inside a ``lax.scan`` over the rows, as the models run it.
The compiled kernel at the cells' widths is ``tests/test_chip_compile.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from picotron_tpu.ops import ssm
from picotron_tpu.ops.pallas import ssm_step as kernel

F32 = jnp.float32
ROWS, SLOTS, HEADS, D_STATE = 3, 3, 32, 128
# (B/C rows of a slot: None one for all heads, d_head, heads a block: None
# the default): Granite's form, Nemotron's (two groups of sixteen heads, a
# block of both groups and one of half a group), SALA's (d_head = d_state)
FORMS = {
    "shared": (None, 16, None),
    "shared_blocks": (None, 16, 8),
    "grouped": (2, 16, None),
    "grouped_half_a_group": (2, 16, 8),
    "per_head": (HEADS, 128, None),
    "per_head_blocks": (HEADS, 128, 8),
}


def operands(form, seed=0, parked=(1,)):
    """A step's operands at toy widths with ``parked`` slots at ``dt = 0``:
    (xs, dt, A, Bm, Cm, leaf)."""
    groups, hd, _ = FORMS[form]
    ks = jax.random.split(jax.random.key(seed), 6)
    bc = (SLOTS, 1, D_STATE) if groups is None \
        else (SLOTS, 1, groups, D_STATE)
    xs = jax.random.normal(ks[0], (SLOTS, 1, HEADS, hd), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (SLOTS, 1, HEADS), F32))
    dt = dt.at[jnp.asarray(parked)].set(0.0)
    A = -jnp.exp(jax.random.normal(ks[2], (HEADS,), F32))
    Bm = jax.random.normal(ks[3], bc, jnp.bfloat16)
    Cm = jax.random.normal(ks[4], bc, jnp.bfloat16)
    leaf = jax.random.normal(ks[5], (ROWS, SLOTS, HEADS, hd, D_STATE), F32)
    return xs, dt, A, Bm, Cm, leaf


def stacked(form, *args):
    return kernel.ssm_step_stacked(*args, block_heads=FORMS[form][2],
                                   interpret=True)


def bit_equal(a, b):
    return np.array_equal(np.asarray(a).view(np.uint32),
                          np.asarray(b).view(np.uint32))


@pytest.mark.parametrize("row", range(ROWS))
@pytest.mark.parametrize("form", sorted(FORMS))
def test_kernel_is_ssm_step_on_the_row_and_leaves_the_rest(form, row):
    xs, dt, A, Bm, Cm, leaf = operands(form)
    y, out = stacked(form, xs, dt, A, Bm, Cm, leaf, jnp.int32(row))
    y_ref, state_ref = ssm.ssm_step(xs, dt, A, Bm, Cm, leaf[row])
    assert y.shape == y_ref.shape and y.dtype == F32
    assert out.shape == leaf.shape and out.dtype == F32
    # the row: the same float32 products and sum a term, so the compiler's
    # choice of a fused multiply-add is the only room; the read-out: a
    # 128-term float32 sum in another order
    np.testing.assert_allclose(out[row], state_ref, rtol=1e-6, atol=1e-6)
    scale = float(jnp.max(jnp.abs(y_ref)))
    np.testing.assert_allclose(y, y_ref, rtol=0, atol=2e-5 * scale)
    for other in range(ROWS):
        if other != row:
            assert bit_equal(out[other], leaf[other]), other
    # the parked slot: exp(0) S + 0 B, bit for bit
    assert bit_equal(out[row, 1], leaf[row, 1])
    assert not bit_equal(out[row, 0], leaf[row, 0])


@pytest.mark.parametrize("form", ["shared", "grouped", "per_head"])
def test_kernel_under_jit_in_a_scan_over_rows(form):
    """As the engine runs it: the leaf a carry of a scan over the layers,
    the row traced, each layer its own operands."""
    per_row = [operands(form, seed=r, parked=(2,)) for r in range(ROWS)]
    leaf = per_row[0][-1]
    xs = tuple(jnp.stack([o[i] for o in per_row]) for i in range(5))

    def run(step, leaf, xs):
        def body(leaf, x):
            row, ops = x
            y, leaf = step(*ops, leaf, row)
            return leaf, y

        return lax.scan(body, leaf, (jnp.arange(ROWS, dtype=jnp.int32), xs))

    got_leaf, got_y = jax.jit(lambda leaf, xs: run(
        lambda *a: stacked(form, *a), leaf, xs))(leaf, xs)
    want_leaf, want_y = jax.jit(lambda leaf, xs: run(
        ssm.ssm_step, leaf, xs))(leaf, xs)
    np.testing.assert_allclose(got_leaf, want_leaf, rtol=1e-6, atol=1e-6)
    scale = float(jnp.max(jnp.abs(want_y)))
    np.testing.assert_allclose(got_y, want_y, rtol=0, atol=2e-5 * scale)
    assert bit_equal(got_leaf[:, 2], leaf[:, 2])  # parked in every layer


def test_off_a_tpu_the_row_form_is_the_elementwise_step():
    """``ssm_step(row=)`` off a TPU: the slice, the step and the update the
    models ran before the kernel, bit for bit."""
    xs, dt, A, Bm, Cm, leaf = operands("grouped")
    y, out = ssm.ssm_step(xs, dt, A, Bm, Cm, leaf, jnp.int32(1))
    y_ref, state_ref = ssm.ssm_step(xs, dt, A, Bm, Cm, leaf[1])
    assert bit_equal(y, y_ref) and bit_equal(out[1], state_ref)
    assert bit_equal(out[0], leaf[0]) and bit_equal(out[2], leaf[2])


@pytest.mark.parametrize("heads,shared,head_bytes,want", [
    (128, 16, 64 * 128 * 4, 32),    # Nemotron: two groups of 16, 1 MiB
    (128, 128, 64 * 128 * 4, 32),   # Granite: one B/C row for all heads
    (32, 1, 128 * 128 * 4, 16),     # SALA: heads of 128 x 128
    (32, 16, 16 * 128 * 4, 32),     # toy: all the heads, two groups
    (12, 12, 1 << 21, 12),          # nothing fits: the fewest allowed
])
def test_head_block_divides_the_heads_and_never_straddles_a_group(
        heads, shared, head_bytes, want):
    hb = kernel.head_block(heads, shared, head_bytes)
    assert hb == want
    assert heads % hb == 0 and (shared % hb == 0 or hb % shared == 0)


def test_kernel_refuses_what_it_cannot_run():
    xs, dt, A, Bm, Cm, leaf = operands("grouped")
    with pytest.raises(ValueError, match="one-row step"):
        kernel.ssm_step_stacked(jnp.tile(xs, (1, 2, 1, 1)), dt, A, Bm, Cm,
                                leaf, 0, interpret=True)
    with pytest.raises(ValueError, match="float32"):
        kernel.ssm_step_stacked(xs, dt, A, Bm, Cm,
                                leaf.astype(jnp.bfloat16), 0, interpret=True)
    with pytest.raises(ValueError, match="blocks of 6 heads"):
        kernel.ssm_step_stacked(xs, dt, A, Bm, Cm, leaf, 0, block_heads=6,
                                interpret=True)
