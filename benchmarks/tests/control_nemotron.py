#!/usr/bin/env python3
"""The serving check's readings for a cell of the Nemotron-H block, by hand
on the chip at the cell's own size (PERF.md has them):

    python3 benchmarks/tests/control_nemotron.py --workload <cell> \\
        --seeds a b c

For each seed it prints the sound program's reading (max |err| / max |logit|
over the check's five positions, the number held to ``TOL_LOGITS_REL``), the
device's peak memory after the weights, the program and the reference, and,
along the sound run's tokens, the readings of the program with one fault
each, which the limit has to lie under:

- ``state_not_carried``: every prefill chunk starts from a zero state and an
  empty conv tail (a prompt's chunks after the first forget what came before
  them);
- ``bc_wrong_group``: a head reads B and C of the next group, not of its own;
- ``gate_norm_over_all``: the gated norm's mean square taken over all of
  ``d_inner`` (Granite's rule), not over each group's channels;
- ``silu_for_relu2``: the experts, routed and shared, run ``silu(x W1) W2``
  in relu^2's place;
- ``up_per_rank_shared_twice``: the latent's way back applied with the shared
  expert inside it, as a chip would that added its shared expert before
  ``W_up``'s partial sums are added: the shared expert counted on the latent
  side too;
- ``state_bf16``: the recurrent state rounded to bfloat16 wherever it is
  stored (the nearest precision below the float32 the configuration states
  for it).

The logits cannot tell a state stored in bfloat16 from the sound program
(every activation beside it is rounded to bfloat16 too), so each reading
comes with ``bf16_exact``: the share of the slot's state entries, after the
check's last decode step, that bfloat16 holds exactly (``control_granite``).

A fault is a wrapper around the block's own function, put in place before
the engine that runs it is built, and taken away after.
"""

import contextlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.tests import control_granite as granite  # noqa: E402

FAULTS = ("state_not_carried", "bc_wrong_group", "gate_norm_over_all",
          "silu_for_relu2", "up_per_rank_shared_twice", "state_bf16")


@contextlib.contextmanager
def fault(name):
    """The block with one fault (None: sound), for the engines built
    inside."""
    import jax
    import jax.numpy as jnp

    from picotron_tpu.models import experts
    from picotron_tpu.models import nemotron_h as nh
    from picotron_tpu.ops.pallas import grouped_experts as grouped

    kept = (nh.mamba_mixer, nh.ssm_scan, nh.ssm_step, nh.rms_norm,
            experts.relu2, grouped._expert, experts.share)
    mixer, scan, step, norm, relu2, kernel_expert, share = kept

    def rounded(x):
        # an explicit op: the compiler drops a convert there and back
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def faulty_mixer(lp, x, conv_in, ssm_in, live, m, one_step):
        if name == "state_not_carried" and not one_step:
            conv_in, ssm_in = jnp.zeros_like(conv_in), jnp.zeros_like(ssm_in)
        if name == "state_bf16":
            ssm_in = rounded(ssm_in)
        out, conv_out, ssm_out = mixer(lp, x, conv_in, ssm_in, live, m,
                                       one_step)
        if name == "state_bf16":
            ssm_out = rounded(ssm_out)
        return out, conv_out, ssm_out

    def next_group(fn):
        return lambda xs, dt, A, Bm, Cm, *rest: fn(
            xs, dt, A, jnp.roll(Bm, 1, axis=2), jnp.roll(Cm, 1, axis=2),
            *rest)

    def norm_over_all(x, w, eps):
        if x.ndim == 4 and w.ndim == 2:  # the gated norm's call alone
            flat = norm(x.reshape(*x.shape[:2], -1), w.reshape(-1), eps)
            return flat.reshape(x.shape)
        return norm(x, w, eps)

    def silu_expert(x, w_up, w_down):
        return jax.nn.silu(x @ w_up) @ w_down

    def silu_kernel(x, w_refs):
        w1_ref, w2_ref = w_refs
        h = jnp.dot(x, w1_ref[...], preferred_element_type=jnp.float32)
        h = jax.nn.silu(h.astype(x.dtype).astype(jnp.float32))
        return jnp.dot(h.astype(x.dtype), w2_ref[...],
                       preferred_element_type=jnp.float32)

    def shared_twice(lp, x2, w_held, routed_in=None, routed_out=None):
        latent_shared = experts.expert(
            x2, lp["ws_up"], lp["ws_down"]) @ lp["latent_down"]
        return share(lp, x2, w_held, routed_in=routed_in,
                     routed_out=lambda y: routed_out(y + latent_shared))

    if name in ("state_not_carried", "state_bf16"):
        nh.mamba_mixer = faulty_mixer
    if name == "bc_wrong_group":
        nh.ssm_scan, nh.ssm_step = next_group(scan), next_group(step)
    if name == "gate_norm_over_all":
        nh.rms_norm = norm_over_all
    if name == "silu_for_relu2":
        experts.relu2, grouped._expert = silu_expert, silu_kernel
    if name == "up_per_rank_shared_twice":
        experts.share = shared_twice
    try:
        yield
    finally:
        (nh.mamba_mixer, nh.ssm_scan, nh.ssm_step, nh.rms_norm,
         experts.relu2, grouped._expert, experts.share) = kept


if __name__ == "__main__":
    # the readings, their record and the summary are ``control_granite``'s
    # (a state beside K/V, ``bf16_exact`` of slot 0's state), run over this
    # block's faults
    granite.fault, granite.FAULTS = fault, FAULTS
    sys.exit(granite.main())
