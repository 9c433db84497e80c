"""picolint suite (ISSUE 9; docs/ANALYSIS.md).

Three layers, mirroring the suite's contract:

1. **Fixture snippets per rule** — for each rule ID a positive snippet
   (the seeded hazard MUST be caught by exactly that rule), a negative
   snippet (the idiomatic near-miss MUST stay silent: precision is what
   keeps the shipped baseline empty), and the suppression comment.
2. **Baseline workflow** — fingerprint matching survives line drift but
   re-opens when the flagged line changes; stale entries are reported;
   undocumented reasons are rejected.
3. **The tier-1 gate** — the repo's own package scans clean against the
   checked-in baseline (every true positive fixed, the baseline reserved
   for documented false positives), in well under the 30s budget, and the
   CLI exit codes enforce it.

The scan is pure ``ast`` — fixtures are never imported or executed, so
they can reference jax/pallas APIs freely without a TPU or even jax.
"""

import json
import textwrap

import pytest

from picotron_tpu.analysis import engine
from picotron_tpu.analysis.findings import (
    RULES, Suppressions, validate_rule_ids)
from picotron_tpu.tools import lint


def _scan(tmp_path, source, name="fix_mod.py"):
    """Write one fixture module and run the full suite over it."""
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    return engine.run_suite(str(tmp_path), [str(p)])


def _rules(findings):
    return sorted({f.rule for f in findings})


# --------------------------------------------------------------------------- #
# PICO-J001: host sync on a traced value
# --------------------------------------------------------------------------- #


def test_j001_float_of_tracer_in_jitted_function(tmp_path):
    found = _scan(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            return float(x) + 1.0
        """)
    assert _rules(found) == ["PICO-J001"]
    assert found[0].context == "f"
    assert "float()" in found[0].message


def test_j001_item_and_device_get_and_np_asarray(tmp_path):
    found = _scan(tmp_path, """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            a = x.item()
            b = np.asarray(x)
            c = jax.device_get(x)
            return a, b, c
        """)
    assert _rules(found) == ["PICO-J001"]
    assert len(found) == 3


def test_j001_bool_coercion_of_array_in_if(tmp_path):
    found = _scan(tmp_path, """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            bad = jnp.any(x > 3)
            if bad:
                return x * 0
            return x
        """)
    assert _rules(found) == ["PICO-J001"]
    assert "bool coercion" in found[0].message


def test_j001_negatives_static_idioms_stay_silent(tmp_path):
    # the idioms jitted code legitimately uses: shape/dtype reads,
    # identity tests on optionals, static config flags, host-scalar
    # annotated params, and a float() on a TRANSITIVE helper's static arg
    found = _scan(tmp_path, """
        import jax
        import jax.numpy as jnp

        def helper(x, scale):
            return x * float(scale)  # scale is a static Python float here

        @jax.jit
        def f(x, cache=None, eps: float = 1e-6, use_flash: bool = False):
            n = x.shape[0]
            d = float(x.ndim + len(x.shape))
            if cache is not None:
                x = x + cache
            if use_flash:
                x = x * 2
            return helper(x, 0.5) + n + d + float(eps)
        """)
    assert found == []


def test_j001_negative_jax_numpy_aliased_as_np(tmp_path):
    # regression: `import jax.numpy as np` rebinds the name — np.asarray
    # is then a traced no-sync op, not host numpy
    found = _scan(tmp_path, """
        import jax
        import jax.numpy as np

        @jax.jit
        def f(x):
            return np.asarray(x) + 1
        """)
    assert found == []


def test_j001_negative_subscript_index_stays_untainted(tmp_path):
    # regression: `out[i] = jnp.sum(a)` taints the container `out`, not
    # the host loop index `i` — `if last:` below is static control flow
    found = _scan(tmp_path, """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x, n: int = 4):
            out = {}
            last = 0
            for i in range(n):
                out[i] = jnp.sum(x)
                last = i
            if last:
                return out[0]
            return out[0] * 2
        """)
    assert found == []


def test_j001_hidden_state_hook_shape(tmp_path):
    """The ISSUE-14 return_hidden hook shape: a jitted verify-like body
    that scans a hidden-state carry and selects the row the traced
    counts point at (take_along_axis over clip(counts - 1)) must stay
    SILENT — all on-device ops; the hazard variant (host-syncing the
    traced hidden/counts with float()/np.asarray inside the program)
    must be caught by exactly J001."""
    found = _scan(tmp_path, """
        import jax
        import jax.numpy as jnp
        from jax import lax

        @jax.jit
        def verify(h, counts):
            def step(carry, x):
                hid = carry
                active = counts > 0
                hid = jnp.where(active[:, None], x, hid)
                return hid, None
            hid, _ = lax.scan(step, h[:, 0], jnp.swapaxes(h, 0, 1))
            idx = jnp.clip(counts - 1, 0, h.shape[1] - 1)[:, None, None]
            return jnp.take_along_axis(h, idx, axis=1)[:, 0], hid
        """)
    assert found == []

    bad = _scan(tmp_path, """
        import jax
        import numpy as np
        import jax.numpy as jnp

        @jax.jit
        def verify(h, counts):
            sel = np.asarray(h)          # host sync on the traced hidden
            return sel[float(counts[0])]  # and on the traced count
        """, name="fix_bad.py")
    assert _rules(bad) == ["PICO-J001"]
    assert len(bad) == 2


def test_j001_dp_shard_occupancy_read_placement(tmp_path):
    """The ISSUE-18 rebalance-planner shape: per-shard occupancy must be
    computed HOST-SIDE from the batcher's slot list, OUTSIDE the jitted
    dispatch (batcher.shard_occupancy) — a plain Python walk, silent.
    The hazard variant reads a TRACED occupancy count inside the
    dp-sharded dispatch (int()/bool-coercion host syncs on the decode
    hot path): exactly J001."""
    found = _scan(tmp_path, """
        import jax
        import jax.numpy as jnp

        def shard_occupancy(slots, slots_per_shard, dp_size):
            # host-side planner input: a walk over the Python slot list,
            # never a device value
            occ = [0] * dp_size
            for i, s in enumerate(slots):
                if s is not None:
                    occ[i // slots_per_shard] += 1
            return occ

        @jax.jit
        def dispatch(params, tokens, budget):
            # the dispatch only consumes traced arrays; occupancy never
            # enters the program
            active = (budget > 0).astype(jnp.int32)
            return tokens * active
        """)
    assert found == []

    bad = _scan(tmp_path, """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def dispatch(tokens, budget, slots_per_shard: int = 2):
            occ = jnp.sum((budget > 0).astype(jnp.int32))
            if int(occ) > slots_per_shard:   # host sync mid-dispatch
                return tokens * 0
            return tokens
        """, name="fix_bad.py")
    assert _rules(bad) == ["PICO-J001"]


# --------------------------------------------------------------------------- #
# PICO-J002: host nondeterminism under trace
# --------------------------------------------------------------------------- #


def test_j002_time_and_np_random_under_trace(tmp_path):
    found = _scan(tmp_path, """
        import time
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            t = time.time()
            r = np.random.rand()
            return x + t + r
        """)
    assert _rules(found) == ["PICO-J002"]
    assert len(found) == 2


def test_j002_negative_host_code_and_jax_random(tmp_path):
    found = _scan(tmp_path, """
        import time
        import jax
        from jax import random

        def host_loop():
            return time.time()  # not traced: fine

        @jax.jit
        def f(x, key):
            return x + random.normal(key, x.shape)  # jax.random: fine
        """)
    assert found == []


def test_j002_through_dotted_import_with_package_init(tmp_path):
    # regression: with pkg/__init__.py in the scan, `pkg` and
    # `pkg.sub.mod` are BOTH scanned modules — `pkg.sub.mod.helper(x)`
    # must resolve helper in the deepest one, not stall at `pkg` and
    # drop the call-graph edge (hiding helper's trace-time hazard)
    pkg = tmp_path / "pkg"
    sub = pkg / "sub"
    sub.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (sub / "__init__.py").write_text("")
    (sub / "mod.py").write_text(textwrap.dedent("""
        import time

        def helper(x):
            return x + time.time()
        """))
    main = tmp_path / "main.py"
    main.write_text(textwrap.dedent("""
        import jax
        import pkg.sub.mod

        @jax.jit
        def f(x):
            return pkg.sub.mod.helper(x)
        """))
    found = engine.run_suite(str(tmp_path), [
        str(pkg / "__init__.py"), str(sub / "__init__.py"),
        str(sub / "mod.py"), str(main)])
    assert _rules(found) == ["PICO-J002"]
    assert "time.time" in found[0].message


# --------------------------------------------------------------------------- #
# PICO-J003: pl.program_id inside a loop body
# --------------------------------------------------------------------------- #


def test_j003_program_id_inside_fori_loop_body(tmp_path):
    found = _scan(tmp_path, """
        from jax import lax
        from jax.experimental import pallas as pl

        def kernel(o_ref):
            def body(j, acc):
                b = pl.program_id(0)  # the decode_attention.py trap
                return acc + b
            o_ref[0] = lax.fori_loop(0, 4, body, 0)
        """)
    assert _rules(found) == ["PICO-J003"]
    assert "program_id" in found[0].message


def test_j003_negative_read_before_the_loop(tmp_path):
    # the fix PR 5 shipped: grid ids read once, the body closes over them
    found = _scan(tmp_path, """
        from jax import lax
        from jax.experimental import pallas as pl

        def kernel(o_ref):
            b = pl.program_id(0)

            def body(j, acc):
                return acc + b
            o_ref[0] = lax.fori_loop(0, 4, body, 0)
        """)
    assert found == []


def test_j003_quant_matmul_shaped_contraction_walk(tmp_path):
    """The quant_matmul kernel pattern (ISSUE 13): a fori_loop contraction
    walk slicing refs with pl.ds. Using program_id to compute the slice
    start INSIDE the body is the hazard variant — J003 must catch it —
    while the shipped shape (ids unused, ds offsets from the loop index
    alone) stays silent."""
    found = _scan(tmp_path, """
        from jax import lax
        from jax.experimental import pallas as pl

        def kernel(x_ref, q_ref, o_ref):
            def body(j, acc):
                n = pl.program_id(1)  # the trap: resolve OUTSIDE the loop
                wb = q_ref[pl.ds(j * 8, 8), pl.ds(n * 8, 8)]
                return acc + x_ref[:, pl.ds(j * 8, 8)] @ wb
            o_ref[:] = lax.fori_loop(0, 4, body, 0.0)
        """)
    assert _rules(found) == ["PICO-J003"]

    clean = _scan(tmp_path, """
        from jax import lax
        from jax.experimental import pallas as pl

        def kernel(x_ref, q_ref, s_ref, o_ref):
            def body(j, acc):
                wb = q_ref[pl.ds(j * 8, 8), :].astype(x_ref.dtype)
                return acc + x_ref[:, pl.ds(j * 8, 8)] @ wb
            acc = lax.fori_loop(0, 4, body, 0.0)
            o_ref[:] = acc * s_ref[0, :]
        """, name="fix_clean.py")
    assert clean == []


def test_j003_lambda_body(tmp_path):
    found = _scan(tmp_path, """
        from jax import lax
        from jax.experimental import pallas as pl

        def kernel(o_ref):
            o_ref[0] = lax.fori_loop(
                0, 4, lambda j, acc: acc + pl.program_id(0), 0)
        """)
    assert _rules(found) == ["PICO-J003"]


def test_j003_ragged_mask_loop_shape(tmp_path):
    """The ISSUE-14 ragged-verify kernel shape: a per-slot fori_loop whose
    body builds a where-mask from the loop index and a valid-count row.
    The shipped form (slot id resolved OUTSIDE the loop, mask from jnp
    ops inside) must stay silent; reading program_id inside the masked
    body is the J003 hazard and must be caught — precision both ways, so
    the baseline stays empty."""
    found = _scan(tmp_path, """
        import jax.numpy as jnp
        from jax import lax
        from jax.experimental import pallas as pl

        def kernel(v_ref, k_ref, o_ref):
            def body(j, acc):
                b = pl.program_id(0)  # the trap: resolve before the loop
                cols = jnp.arange(8)
                rows = jnp.where(cols < v_ref[b], cols, 8)
                return acc + k_ref[pl.ds(j * 8, 8), :] * rows[:, None]
            o_ref[:] = lax.fori_loop(0, 4, body, 0.0)
        """)
    assert _rules(found) == ["PICO-J003"]

    clean = _scan(tmp_path, """
        import jax.numpy as jnp
        from jax import lax
        from jax.experimental import pallas as pl

        def kernel(v_ref, k_ref, o_ref):
            b = pl.program_id(0)
            valid = v_ref[b]

            def body(j, acc):
                cols = jnp.arange(8)
                rows = jnp.where(cols < valid, cols, 8)
                return acc + k_ref[pl.ds(j * 8, 8), :] * rows[:, None]
            o_ref[:] = lax.fori_loop(0, 4, body, 0.0)
        """, name="fix_clean.py")
    assert clean == []


# --------------------------------------------------------------------------- #
# PICO-J005: make_async_copy started without a reachable wait
# --------------------------------------------------------------------------- #


def test_j005_start_without_wait(tmp_path):
    found = _scan(tmp_path, """
        from jax.experimental.pallas import tpu as pltpu

        def kernel(src_ref, buf, sem, o_ref):
            dma = pltpu.make_async_copy(src_ref, buf, sem)
            dma.start()  # nothing ever waits: buf read mid-flight
            o_ref[0] = buf[0]
        """)
    assert _rules(found) == ["PICO-J005"]
    assert "wait" in found[0].message


def test_j005_start_in_loop_body_wait_outside(tmp_path):
    # the exact double-buffering hazard: a per-iteration start whose only
    # wait sits after the loop — N starts against 1 wait
    found = _scan(tmp_path, """
        from jax import lax
        from jax.experimental.pallas import tpu as pltpu

        def kernel(src_ref, buf, sem, o_ref):
            def body(j, acc):
                pltpu.make_async_copy(src_ref.at[j], buf, sem).start()
                return acc + buf[0]
            acc = lax.fori_loop(0, 4, body, 0.0)
            pltpu.make_async_copy(src_ref.at[0], buf, sem).wait()
            o_ref[0] = acc
        """)
    assert _rules(found) == ["PICO-J005"]
    assert "loop" in found[0].message


def test_j005_negative_paired_double_buffer_idiom(tmp_path):
    # the shipped decode-kernel shape: start/wait pairs built from the
    # same triples by sibling helper closures, warm-up start outside the
    # loop, per-iteration prefetch + wait inside — silent
    found = _scan(tmp_path, """
        from jax import lax
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def kernel(src_ref, buf, sems, o_ref):
            def start(j, slot):
                pltpu.make_async_copy(src_ref.at[j], buf.at[slot],
                                      sems.at[slot]).start()

            def wait(j, slot):
                pltpu.make_async_copy(src_ref.at[j], buf.at[slot],
                                      sems.at[slot]).wait()
                return buf[slot]

            def body(j, acc):
                slot = lax.rem(j, 2)

                @pl.when(j + 1 < 4)
                def _():
                    start(j + 1, 1 - slot)
                return acc + wait(j, slot)[0]

            start(0, 0)
            o_ref[0] = lax.fori_loop(0, 4, body, 0.0)
        """)
    assert found == []


def test_j003_segmented_gather_adapter_walk(tmp_path):
    """The ISSUE-16 segmented multi-LoRA matmul shape
    (ops/pallas/lora_matmul.py): a per-row grid whose A/B blocks are
    steered by a scalar-prefetch adapter-id vector. The shipped form
    resolves the row id via the BlockSpec index maps — the kernel body
    never reads program_id at all — and an in-body rank-chunk walk that
    re-reads program_id per iteration to re-derive the adapter row is
    the J003 hazard. Precision both ways keeps the baseline empty."""
    found = _scan(tmp_path, """
        from jax import lax
        from jax.experimental import pallas as pl

        def kernel(ids_ref, x_ref, a_ref, b_ref, o_ref):
            def body(j, acc):
                bi = pl.program_id(0)  # the trap: the index maps own this
                t = ids_ref[bi]
                ab = a_ref[t, pl.ds(j * 8, 8), :]
                return acc + x_ref[0, :, pl.ds(j * 8, 8)] @ ab
            o_ref[0] = lax.fori_loop(0, 4, body, 0.0) @ b_ref[0]
        """)
    assert _rules(found) == ["PICO-J003"]

    clean = _scan(tmp_path, """
        from jax import lax
        from jax.experimental import pallas as pl

        def kernel(ids_ref, x_ref, a_ref, b_ref, o_ref):
            def body(j, acc):
                ab = a_ref[0, pl.ds(j * 8, 8), :]
                return acc + x_ref[0, :, pl.ds(j * 8, 8)] @ ab
            t = lax.fori_loop(0, 4, body, 0.0)
            o_ref[0] = t @ b_ref[0]
        """, name="fix_clean.py")
    assert clean == []


def test_j005_segmented_gather_hand_rolled_dma(tmp_path):
    """The hand-rolled variant lora_matmul.py avoids: DMA-ing each row's
    chosen adapter pair into VMEM scratch inside a per-row loop. A
    per-iteration start whose only wait sits after the loop is the J005
    hazard; the paired in-body start+wait (serial gather) stays silent —
    the shipped kernel needs neither because scalar-prefetch index maps
    do the steering."""
    found = _scan(tmp_path, """
        from jax import lax
        from jax.experimental.pallas import tpu as pltpu

        def kernel(ids_ref, pack_ref, buf, sem, o_ref):
            def body(j, acc):
                pltpu.make_async_copy(pack_ref.at[ids_ref[j]], buf,
                                      sem).start()
                return acc + buf[0]
            acc = lax.fori_loop(0, 4, body, 0.0)
            pltpu.make_async_copy(pack_ref.at[0], buf, sem).wait()
            o_ref[0] = acc
        """)
    assert _rules(found) == ["PICO-J005"]

    clean = _scan(tmp_path, """
        from jax import lax
        from jax.experimental.pallas import tpu as pltpu

        def kernel(ids_ref, pack_ref, buf, sem, o_ref):
            def body(j, acc):
                dma = pltpu.make_async_copy(pack_ref.at[ids_ref[j]], buf,
                                            sem)
                dma.start()
                dma.wait()
                return acc + buf[0]
            o_ref[0] = lax.fori_loop(0, 4, body, 0.0)
        """, name="fix_clean.py")
    assert clean == []


def test_j005_negative_thread_start_and_serial_pair(tmp_path):
    # receiver typing: thread.start()/event.wait() are not DMAs; a serial
    # in-body start+wait pair is the pre-pipelining idiom and stays silent
    found = _scan(tmp_path, """
        import threading
        from jax import lax
        from jax.experimental.pallas import tpu as pltpu

        def host():
            t = threading.Thread(target=print)
            t.start()

        def kernel(src_ref, buf, sem, o_ref):
            def body(j, acc):
                dma = pltpu.make_async_copy(src_ref.at[j], buf, sem)
                dma.start()
                dma.wait()
                return acc + buf[0]
            o_ref[0] = lax.fori_loop(0, 4, body, 0.0)
        """)
    assert found == []


# --------------------------------------------------------------------------- #
# PICO-J004: jit/pallas_call constructed inside a loop
# --------------------------------------------------------------------------- #


def test_j004_jit_built_per_iteration(tmp_path):
    found = _scan(tmp_path, """
        import jax

        def build(fns, x):
            out = []
            for fn in fns:
                out.append(jax.jit(fn)(x))  # fresh callable every pass
            return out
        """)
    assert _rules(found) == ["PICO-J004"]
    assert "recompile" in found[0].message


def test_j004_page_transport_shaped_export_loop(tmp_path):
    """The ISSUE-15 page-transport shape: the export walks pinned pages
    through a jitted dynamic-slice gather. Building the jit INSIDE the
    per-page loop is the J004 hazard (a recompile per exported page);
    the shipped form — slice/write jits built once at engine
    construction, the loop calling the hoisted executables — must stay
    silent. Precision both ways, so the baseline stays empty."""
    found = _scan(tmp_path, """
        import jax
        from jax import lax

        def slice_page(cache, pid):
            return {n: lax.dynamic_slice_in_dim(a, pid, 1, axis=1)
                    for n, a in cache.items()}

        def export(cache, pids):
            out = []
            for pid in pids:
                out.append(jax.jit(slice_page)(cache, pid))  # per page!
            return out
        """)
    assert _rules(found) == ["PICO-J004"]

    clean = _scan(tmp_path, """
        import jax
        from jax import lax

        def slice_page(cache, pid):
            return {n: lax.dynamic_slice_in_dim(a, pid, 1, axis=1)
                    for n, a in cache.items()}

        SLICE = jax.jit(slice_page)

        def export(cache, pids):
            return [SLICE(cache, pid) for pid in pids]
        """, name="fix_clean.py")
    assert clean == []


def test_j004_negative_jit_in_for_iterator_expression(tmp_path):
    # regression: the iterator expression runs ONCE at loop setup —
    # `for batch in loader_of(jax.jit(step)):` must not fire; a jit in
    # a while TEST re-evaluates per pass and must
    found = _scan(tmp_path, """
        import jax

        def loader_of(step):
            return [step]

        def run(step):
            for batch in loader_of(jax.jit(step)):
                batch()
        """)
    assert found == []
    found = _scan(tmp_path, """
        import jax

        def run(step, x):
            while jax.jit(step)(x):
                x = x - 1
        """)
    assert _rules(found) == ["PICO-J004"]


def test_j004_negative_hoisted_jit_and_def_in_loop(tmp_path):
    found = _scan(tmp_path, """
        import jax

        def build(fns, xs):
            jitted = [jax.jit(f) for f in fns]  # comprehension, not a loop stmt

            def apply(x):
                return jax.jit(step)(x)  # built per CALL, not per iteration

            out = []
            for x in xs:
                out.append(jitted[0](x))
            return out

        def step(x):
            return x
        """)
    assert found == []


# --------------------------------------------------------------------------- #
# PICO-J006: model program dispatched outside _dispatch
# --------------------------------------------------------------------------- #


def test_j006_program_called_outside_dispatch(tmp_path):
    found = _scan(tmp_path, """
        class Engine:
            def _dispatch(self, call):
                return call()

            def decode(self, params, cache):
                return self._decode_jit(params, cache)
        """)
    assert _rules(found) == ["PICO-J006"]
    assert found[0].context == "Engine.decode"
    assert "_decode_jit" in found[0].message
    assert "_dispatch" in found[0].message


def test_j006_negative_routed_through_dispatch(tmp_path):
    found = _scan(tmp_path, """
        class Engine:
            def _dispatch(self, call):
                return call()

            def decode(self, params, cache):
                return self._dispatch(lambda: self._decode_jit(params, cache))

            def verify(self, params, cache):
                return self._dispatch(
                    call=lambda: self._verify_prog(params, cache))
        """)
    assert found == []


def test_j006_negative_housekeeping_and_builders(tmp_path):
    # Housekeeping jits take the cache (or nothing) first — not model
    # dispatches.  `_make_*` builders construct rather than run programs.
    found = _scan(tmp_path, """
        class Engine:
            def _dispatch(self, call):
                return call()

            def setup(self, params, cache, slot):
                self._decode_jit = self._make_decode_jit(params)
                cache = self._init_cache_jit(cache)
                cache = self._set_length_jit(cache, slot)
                return cache
        """)
    assert found == []


def test_j006_negative_class_without_dispatch(tmp_path):
    # The rule only binds classes that define the fault wrapper.
    found = _scan(tmp_path, """
        class Helper:
            def decode(self, params, cache):
                return self._decode_jit(params, cache)
        """)
    assert found == []


def test_j006_mixed_routed_and_direct_in_one_class(tmp_path):
    found = _scan(tmp_path, """
        class Engine:
            def _dispatch(self, call):
                try:
                    return call()
                except RuntimeError:
                    return call()

            def good(self, params, cache):
                return self._dispatch(lambda: self._block_jit(params, cache))

            def bad(self, params, cache):
                out = self._verify_jit(params, cache)
                return out
        """)
    assert _rules(found) == ["PICO-J006"]
    assert len(found) == 1
    assert found[0].context == "Engine.bad"
    assert "self._verify_jit" in found[0].snippet


# --------------------------------------------------------------------------- #
# PICO-C001: lock-order inversion
# --------------------------------------------------------------------------- #

_C001_FIXTURE = """
    import threading

    class Inverted:
        def __init__(self):
            self.a_mu = threading.Lock()
            self.b_mu = threading.Lock()
            self.x = 0

        def one(self):
            with self.a_mu:
                with self.b_mu:
                    self.x = 1

        def two(self):
            with self.b_mu:
                with self.a_mu:
                    self.x = 2
    """


def test_c001_lock_order_inversion(tmp_path):
    found = _scan(tmp_path, _C001_FIXTURE)
    assert _rules(found) == ["PICO-C001"]
    assert len(found) == 1  # one inversion, reported once
    assert "opposite" in found[0].message


def test_c001_negative_consistent_order_and_transitive(tmp_path):
    # same nesting everywhere — including through a same-class call — is
    # a hierarchy, not an inversion
    found = _scan(tmp_path, """
        import threading

        class Ordered:
            def __init__(self):
                self.a_mu = threading.Lock()
                self.b_mu = threading.Lock()
                self.x = 0

            def one(self):
                with self.a_mu:
                    with self.b_mu:
                        self.x = 1

            def two(self):
                with self.a_mu:
                    self._locked_tail()

            def _locked_tail(self):
                with self.b_mu:
                    self.x = 2
        """)
    assert found == []


def test_c001_transitive_inversion_through_method_call(tmp_path):
    # one path nests a->b lexically; the other holds b and CALLS a method
    # that takes a — the deadlock picolint exists to catch (the PR 6
    # _next_uid-under-_mu incident shape)
    found = _scan(tmp_path, """
        import threading

        class Transitive:
            def __init__(self):
                self.a_mu = threading.Lock()
                self.b_mu = threading.Lock()
                self.x = 0

            def one(self):
                with self.a_mu:
                    with self.b_mu:
                        self.x = 1

            def two(self):
                with self.b_mu:
                    self._take_a()

            def _take_a(self):
                with self.a_mu:
                    self.x = 2
        """)
    assert "PICO-C001" in _rules(found)


# --------------------------------------------------------------------------- #
# PICO-C002: blocking call while holding a lock
# --------------------------------------------------------------------------- #


def test_c002_sleep_under_lock(tmp_path):
    found = _scan(tmp_path, """
        import threading
        import time

        class Sleeper:
            def __init__(self):
                self._mu = threading.Lock()

            def hold(self):
                with self._mu:
                    time.sleep(0.5)
        """)
    assert _rules(found) == ["PICO-C002"]
    assert "time.sleep" in found[0].message


def test_c002_blocking_io_and_join_under_lock(tmp_path):
    found = _scan(tmp_path, """
        import shutil
        import threading

        class Copier:
            def __init__(self):
                self._mu = threading.Lock()
                self._worker = None

            def hold(self, src, dst):
                with self._mu:
                    shutil.copytree(src, dst)
                    self._worker.join()
        """)
    assert _rules(found) == ["PICO-C002"]
    assert len(found) == 2


def test_c002_negative_str_join_under_lock(tmp_path):
    # regression: `sep.join(parts)` is string building (one iterable
    # arg), not a thread join — `t.join(5)` (numeric timeout) still is
    found = _scan(tmp_path, """
        import threading

        class S:
            def __init__(self):
                self._mu = threading.Lock()
                self.sep = ","
                self.parts = []
                self.worker = threading.Thread(target=self.render)

            def render(self):
                with self._mu:
                    return self.sep.join(self.parts)

            def stop(self):
                with self._mu:
                    self.worker.join(5)
        """)
    assert _rules(found) == ["PICO-C002"]
    assert all("worker.join" in f.message for f in found)


def test_c002_one_hop_propagation_and_negatives(tmp_path):
    # sleep in a LOCK-FREE callee is fine alone, a hazard when the caller
    # holds the lock across the call; str.join and os.path.join are not
    # blocking calls
    found = _scan(tmp_path, """
        import os
        import threading
        import time

        class Indirect:
            def __init__(self):
                self._mu = threading.Lock()

            def _backoff(self):
                time.sleep(0.1)  # lock-free here: fine

            def hold(self):
                with self._mu:
                    self._backoff()

            def harmless(self, parts):
                with self._mu:
                    a = ",".join(str(p) for p in parts)
                    return os.path.join(a, "x")
        """)
    assert _rules(found) == ["PICO-C002"]
    assert len(found) == 1
    assert "_backoff" in found[0].message


# --------------------------------------------------------------------------- #
# PICO-C003: guarded attribute mutated outside its lock
# --------------------------------------------------------------------------- #

_C003_FIXTURE = """
    import threading

    class Counter:
        def __init__(self):
            self._mu = threading.Lock()
            self.count = 0

        def locked_inc(self):
            with self._mu:
                self.count += 1

        def unlocked_inc(self):
            self.count += 1  # the serve.py rejections incident shape
    """


def test_c003_mutation_outside_the_guarding_lock(tmp_path):
    found = _scan(tmp_path, _C003_FIXTURE)
    assert _rules(found) == ["PICO-C003"]
    assert found[0].context == "Counter.unlocked_inc"


def test_c003_negatives_init_and_consistent_guarding(tmp_path):
    # __init__ runs before any thread exists; queues/events are the
    # sanctioned channels; consistently-guarded attrs are clean
    found = _scan(tmp_path, """
        import queue
        import threading

        class Clean:
            def __init__(self):
                self._mu = threading.Lock()
                self.count = 0
                self.inbox = queue.Queue()

            def inc(self):
                with self._mu:
                    self.count += 1

            def push(self, item):
                self.inbox.put(item)
        """)
    assert found == []


def test_c003_stats_scrape_scratch_fields_regression(tmp_path):
    """The batcher stats() race this repo shipped (and fixed alongside
    the overlap pipeline): the dispatch loop wrote ``_host_sync_s`` /
    ``_last_prefill`` bare while a server thread's stats() scrape read
    them — once the scrape takes a leaf lock, the loop's bare writes are
    exactly C003's mutated-outside-the-guarding-lock shape. The fixture
    mirrors inference/batcher.py's fields so a relapse trips here."""
    found = _scan(tmp_path, """
        import threading

        class Batcher:
            def __init__(self):
                self._scratch_mu = threading.Lock()
                self._host_sync_s = 0.0
                self._last_prefill = {}

            def _sync_round(self, dt):
                with self._scratch_mu:
                    self._host_sync_s = dt

            def _fallback_round(self, dt):
                self._host_sync_s = dt  # one path missed: the relapse

            def stats(self):
                with self._scratch_mu:
                    return {"last_host_sync_s": self._host_sync_s,
                            "last_prefill": dict(self._last_prefill)}
        """)
    assert _rules(found) == ["PICO-C003"]
    assert found[0].context == "Batcher._fallback_round"


def test_c003_negative_scratch_snapshots_under_leaf_lock(tmp_path):
    """The FIXED batcher shape stays clean: every write of the scratch
    fields and the scrape's snapshot sit under the same leaf lock, and
    the blocking device sync (C002's concern) happens OUTSIDE it — the
    lock wraps only the dict copy and float store."""
    found = _scan(tmp_path, """
        import threading
        import time

        class Batcher:
            def __init__(self):
                self._scratch_mu = threading.Lock()
                self._host_sync_s = 0.0
                self._last_prefill = {}

            def _sync_round(self, materialize, t0):
                materialize()       # device sync: blocks, lock-free
                time.sleep(0.001)   # synthetic device window: lock-free
                with self._scratch_mu:
                    self._host_sync_s = time.monotonic() - t0

            def _prefill(self, info):
                with self._scratch_mu:
                    self._last_prefill = dict(info)

            def stats(self):
                with self._scratch_mu:
                    return {"last_host_sync_s": self._host_sync_s,
                            "last_prefill": dict(self._last_prefill)}
        """)
    assert found == []


def test_c003_lock_acquired_inside_a_with_body_outlives_it(tmp_path):
    """serve.py's submit() times its bounded acquire under a span: the
    lock taken by ``acquire()`` INSIDE the with-body is held after the
    block (the block's own context managers are not), so the guarded
    writes that follow are clean — and the same write with no acquire
    anywhere before it is still flagged."""
    src = """
        import threading

        class Front:
            def __init__(self):
                self._mu = threading.Lock()
                self._span_mu = threading.Lock()
                self._waiters = {{}}

            def _loop(self):
                with self._mu:
                    self._waiters.pop("k", None)

            def submit(self, uid, timed):
                with timed, self._span_mu:
                    {acquire}
                try:
                    self._waiters[uid] = 1
                finally:
                    self._mu.release()
        """
    held = _scan(tmp_path, src.format(acquire=(
        "if not self._mu.acquire(timeout=10.0):\n"
        "                        raise RuntimeError('stalled')")))
    assert held == []
    # the same acquire in slices: the loop ends when one succeeds
    sliced = _scan(tmp_path, src.format(acquire=(
        "while not self._mu.acquire(timeout=10.0):\n"
        "                        if self.stalled:\n"
        "                            raise RuntimeError('stalled')")))
    assert sliced == []
    bare = _scan(tmp_path, src.format(acquire="pass"))
    assert _rules(bare) == ["PICO-C003"]
    assert bare[0].context == "Front.submit"


def test_c002_positive_device_sync_under_scratch_lock(tmp_path):
    """The tempting wrong fix for the stats() race — wrap the whole sync
    stage, blocking wait included, in the scratch lock — trades a race
    for a stalled scrape plane: C002 flags the sleep held under the
    lock, which is why the leaf lock wraps only the snapshot."""
    found = _scan(tmp_path, """
        import threading
        import time

        class Batcher:
            def __init__(self):
                self._scratch_mu = threading.Lock()
                self._host_sync_s = 0.0

            def _sync_round(self, t0):
                with self._scratch_mu:
                    time.sleep(0.001)  # blocking under the leaf lock
                    self._host_sync_s = time.monotonic() - t0

            def stats(self):
                with self._scratch_mu:
                    return {"last_host_sync_s": self._host_sync_s}
        """)
    assert "PICO-C002" in _rules(found)


def test_c003_negative_thread_starting_method_is_exempt(tmp_path):
    # regression: writes in the method that STARTS the worker thread
    # happen-before Thread.start, same as __init__ (module docstring
    # contract) — resetting state there needs no lock
    found = _scan(tmp_path, """
        import threading

        class Counter:
            def __init__(self):
                self._mu = threading.Lock()
                self.count = 0

            def start(self):
                self.count = 0
                threading.Thread(target=self._worker, daemon=True).start()

            def _worker(self):
                with self._mu:
                    self.count += 1
        """)
    assert found == []


# --------------------------------------------------------------------------- #
# PICO-C004: cross-thread mutation with no lock anywhere
# --------------------------------------------------------------------------- #


def test_c004_worker_and_foreground_mutate_unlocked(tmp_path):
    found = _scan(tmp_path, """
        import threading

        class Mirror:
            def __init__(self):
                self.errs = []

            def start(self):
                threading.Thread(target=self._worker, daemon=True).start()

            def _worker(self):
                self.errs.append("boom")  # the checkpoint.py incident shape

            def drain(self):
                out, self.errs = self.errs, []
                return out
        """)
    assert _rules(found) == ["PICO-C004"]
    assert "_worker" in found[0].context


def test_c004_negative_lock_on_both_sides(tmp_path):
    found = _scan(tmp_path, """
        import threading

        class Guarded:
            def __init__(self):
                self._mu = threading.Lock()
                self.errs = []

            def start(self):
                threading.Thread(target=self._worker, daemon=True).start()

            def _worker(self):
                with self._mu:
                    self.errs.append("boom")

            def drain(self):
                with self._mu:
                    out, self.errs = self.errs, []
                return out
        """)
    assert found == []


# --------------------------------------------------------------------------- #
# suppression comments
# --------------------------------------------------------------------------- #


def test_suppression_on_the_flagged_line(tmp_path):
    found = _scan(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            return float(x)  # picolint: disable=PICO-J001
        """)
    assert found == []


def test_suppression_bare_suffix_and_file_scope(tmp_path):
    found = _scan(tmp_path, """
        # picolint: disable-file=C002
        import threading
        import time

        class Sleeper:
            def __init__(self):
                self._mu = threading.Lock()

            def hold(self):
                with self._mu:
                    time.sleep(0.5)
        """)
    assert found == []


def test_suppression_is_rule_specific(tmp_path):
    # disabling one rule must not swallow another rule's finding there
    found = _scan(tmp_path, """
        import jax
        import time

        @jax.jit
        def f(x):
            t = time.time() + float(x)  # picolint: disable=PICO-J002
            return t
        """)
    assert _rules(found) == ["PICO-J001"]


def test_suppression_parsing_and_rule_validation():
    sup = Suppressions.parse(
        "x = 1  # picolint: disable=J001, PICO-C002\n"
        "# picolint: disable-file=all\n")
    assert sup.by_line[1] == {"PICO-J001", "PICO-C002"}
    assert sup.whole_file == {"*"}
    assert validate_rule_ids(["PICO-J001", "*"]) is None
    assert validate_rule_ids(["PICO-J001", "PICO-Z999"]) == "PICO-Z999"


# --------------------------------------------------------------------------- #
# baseline workflow
# --------------------------------------------------------------------------- #


def _write_baseline(path, entries):
    path.write_text(json.dumps({"findings": entries}, indent=2))


def test_baseline_matches_by_fingerprint_not_line(tmp_path):
    src = """
        import jax

        @jax.jit
        def f(x):
            return float(x)
        """
    found = _scan(tmp_path, src)
    assert len(found) == 1
    bl = tmp_path / "baseline.json"
    _write_baseline(bl, [engine.baseline_entry(
        found[0], reason="fixture: demonstrating the baseline contract")])

    # line drift above the finding does not re-open it
    drifted = "# a new leading comment\n# another\n" + textwrap.dedent(src)
    (tmp_path / "fix_mod.py").write_text(drifted)
    out = engine.run(str(tmp_path), [str(tmp_path / "fix_mod.py")],
                     baseline_path=str(bl))
    assert out["counts"] == {"total": 1, "new": 0, "baselined": 1,
                             "stale_baseline": 0}

    # editing the FLAGGED line re-opens the finding and stales the entry
    edited = drifted.replace("float(x)", "float(x * 2)")
    (tmp_path / "fix_mod.py").write_text(edited)
    out = engine.run(str(tmp_path), [str(tmp_path / "fix_mod.py")],
                     baseline_path=str(bl))
    assert out["counts"]["new"] == 1
    assert out["counts"]["stale_baseline"] == 1


def test_baseline_undocumented_reasons_are_rejected():
    entries = [
        {"rule": "PICO-J001", "path": "a.py", "context": "f",
         "snippet": "x", "reason": "identity test on a static optional"},
        {"rule": "PICO-J001", "path": "b.py", "context": "g",
         "snippet": "y", "reason": ""},
        {"rule": "PICO-J001", "path": "c.py", "context": "h",
         "snippet": "z", "reason": "TODO: document why"},
    ]
    bad = engine.undocumented_entries(entries)
    assert [e["path"] for e in bad] == ["b.py", "c.py"]


def test_baseline_duplicate_fingerprints_are_counted(tmp_path):
    # two identical findings against ONE baseline entry: one stays new
    src = """
        import jax

        @jax.jit
        def f(x, flip=None):
            if flip is None:
                return float(x)
            return float(x)
        """
    found = _scan(tmp_path, src)
    assert len(found) == 2
    assert found[0].fingerprint() == found[1].fingerprint()
    new, matched, stale = engine.diff_baseline(
        found, [engine.baseline_entry(found[0], reason="fixture")])
    assert len(new) == 1 and len(matched) == 1 and stale == []


# --------------------------------------------------------------------------- #
# CLI
# --------------------------------------------------------------------------- #


def test_cli_exit_codes_and_json(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import jax

        @jax.jit
        def f(x):
            return float(x)
        """))
    clean = tmp_path / "clean.py"
    clean.write_text("def f(x):\n    return x\n")
    bl = str(tmp_path / "baseline.json")

    assert lint.main([str(bad), "--baseline", bl]) == 1
    capsys.readouterr()
    assert lint.main([str(clean), "--baseline", bl]) == 0
    capsys.readouterr()
    assert lint.main([str(bad), "--baseline", bl,
                      "--no-fail-on-new"]) == 0
    capsys.readouterr()

    assert lint.main([str(bad), "--baseline", bl, "--json"]) == 1
    report = json.loads(capsys.readouterr().out)
    assert report["tool"] == "picolint"
    assert report["counts"]["new"] == 1
    assert report["new"][0]["rule"] == "PICO-J001"
    assert set(report["rules"]) == set(RULES)

    assert lint.main(["--rules", "PICO-NOPE"]) == 2
    assert lint.main([str(tmp_path / "missing.py")]) == 2


def test_cli_rules_narrows_report_not_the_gate(tmp_path, capsys):
    # regression: --rules filters what is PRINTED; the exit-code gate
    # still fails on new findings from every other rule
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import jax

        @jax.jit
        def f(x):
            return float(x)
        """))
    bl = str(tmp_path / "baseline.json")
    assert lint.main([str(bad), "--baseline", bl,
                      "--rules", "PICO-C002", "--json"]) == 1
    report = json.loads(capsys.readouterr().out)
    assert report["counts"]["new"] == 0  # J001 hidden from the report...
    # ...but the run still failed, so --rules cannot launder a finding


def test_cli_baselined_count_uses_the_budget_split(tmp_path, capsys):
    # two findings with the SAME fingerprint (same snippet text +
    # context, different lines) against one baseline entry: the CLI
    # report must carry diff_baseline's budget split through — exactly
    # one baselined, one new — not re-derive matched on its own
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import jax

        @jax.jit
        def f(x):
            y = float(x)
            y = float(x)
            return y
        """))
    bl = tmp_path / "baseline.json"
    findings = engine.run_suite(str(tmp_path), [str(bad)])
    assert len(findings) == 2
    assert findings[0].fingerprint() == findings[1].fingerprint()
    bl.write_text(json.dumps(
        {"findings": [engine.baseline_entry(
            findings[0], reason="fixture: one of the two is baselined")]}))
    assert lint.main([str(bad), "--baseline", str(bl), "--json"]) == 1
    report = json.loads(capsys.readouterr().out)
    assert report["counts"]["baselined"] == 1
    assert report["counts"]["new"] == 1


def test_cli_malformed_baseline_is_a_usage_error(tmp_path, capsys):
    # regression: a baseline object without "findings" must exit 2 with
    # a descriptive message, not crash with a raw KeyError
    bl = tmp_path / "baseline.json"
    bl.write_text('{"entries": []}')
    clean = tmp_path / "clean.py"
    clean.write_text("def f(x):\n    return x\n")
    assert lint.main([str(clean), "--baseline", str(bl)]) == 2
    assert "findings" in capsys.readouterr().err


def test_cli_root_is_stable_across_invocation_shapes(tmp_path, capsys):
    # regression: out-of-repo, `lint proj` and `lint proj/bad.py` must
    # report the same file under the same relative path — fingerprints
    # (and so baselines) would otherwise churn with the invocation shape
    proj = tmp_path / "proj"
    (proj / "pkg").mkdir(parents=True)
    (proj / "pkg" / "other.py").write_text("def g(x):\n    return x\n")
    (proj / "bad.py").write_text(textwrap.dedent("""
        import jax

        @jax.jit
        def f(x):
            return float(x)
        """))
    bl = str(tmp_path / "baseline.json")
    paths = []
    for spec in ([str(proj)], [str(proj / "bad.py")]):
        assert lint.main(spec + ["--baseline", bl, "--json"]) == 1
        report = json.loads(capsys.readouterr().out)
        paths.append(report["new"][0]["path"])
    assert paths[0] == paths[1] == "bad.py"


def test_cli_partial_scan_does_not_stale_out_of_scope_entries(tmp_path,
                                                              capsys):
    # regression: a baseline entry for a file the scan did not cover is
    # not evidence the entry is dead — only a scan that includes the
    # file may call it stale
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import jax

        @jax.jit
        def f(x):
            return float(x)
        """))
    other = tmp_path / "other.py"
    other.write_text("def g(x):\n    return x\n")
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"findings": [{
        "rule": "PICO-C002", "path": "other.py", "context": "X.m",
        "snippet": "time.sleep(1)",
        "reason": "fixture: documented entry for an unscanned file"}]}))
    assert lint.main([str(bad), "--baseline", str(bl), "--json"]) == 1
    report = json.loads(capsys.readouterr().out)
    assert report["counts"]["stale_baseline"] == 0  # other.py not scanned
    assert lint.main([str(bad), str(other), "--baseline", str(bl),
                      "--json"]) == 1
    report = json.loads(capsys.readouterr().out)
    assert report["counts"]["stale_baseline"] == 1  # scanned and clean


def test_cli_rules_canonicalize_like_suppressions(tmp_path, capsys):
    # regression: `--rules j001` spells the same as a suppression
    # comment; `--rules '*'` means every rule, not an empty report
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import jax

        @jax.jit
        def f(x):
            return float(x)
        """))
    bl = str(tmp_path / "baseline.json")
    assert lint.main([str(bad), "--baseline", bl,
                      "--rules", "j001", "--json"]) == 1
    report = json.loads(capsys.readouterr().out)
    assert report["counts"]["new"] == 1
    assert lint.main([str(bad), "--baseline", bl,
                      "--rules", "*", "--json"]) == 1
    report = json.loads(capsys.readouterr().out)
    assert report["counts"]["new"] == 1


def test_cli_empty_scope_scans_nothing(tmp_path, capsys):
    # regression: a directory with no .py files must scan ZERO files,
    # not silently fall back to the whole repo
    empty = tmp_path / "empty"
    empty.mkdir()
    (empty / "README.txt").write_text("no python here")
    assert lint.main([str(empty), "--baseline",
                      str(tmp_path / "baseline.json"), "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["counts"]["total"] == 0


def test_cli_write_baseline_roundtrip(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import jax

        @jax.jit
        def f(x):
            return float(x)
        """))
    bl = tmp_path / "baseline.json"
    # --write-baseline records the finding (exit 0) with a placeholder
    # reason that the documentation gate then rejects until filled in
    assert lint.main([str(bad), "--baseline", str(bl),
                      "--write-baseline"]) == 0
    capsys.readouterr()
    entries = engine.load_baseline(str(bl))
    assert len(entries) == 1
    assert engine.undocumented_entries(entries) == entries
    # once baselined, the same scan is clean
    assert lint.main([str(bad), "--baseline", str(bl)]) == 0


# --------------------------------------------------------------------------- #
# the tier-1 gate: the repo's own tree is clean
# --------------------------------------------------------------------------- #


def test_seeded_hazards_each_caught_by_exactly_their_rule(tmp_path):
    """The acceptance fixtures from ISSUE 9, one rule each."""
    cases = {
        "PICO-J003": """
            from jax import lax
            from jax.experimental import pallas as pl

            def kernel(o_ref):
                def body(j, acc):
                    return acc + pl.program_id(0)
                o_ref[0] = lax.fori_loop(0, 4, body, 0)
            """,
        "PICO-J001": """
            import jax

            @jax.jit
            def f(x):
                return float(x)
            """,
        "PICO-C001": _C001_FIXTURE,
        "PICO-C002": """
            import threading
            import time

            class S:
                def __init__(self):
                    self._mu = threading.Lock()

                def hold(self):
                    with self._mu:
                        time.sleep(1.0)
            """,
    }
    for rule, src in cases.items():
        found = _scan(tmp_path, src, name=f"{rule.lower().replace('-', '_')}.py")
        assert _rules(found) == [rule], (
            f"seeded {rule} fixture found {_rules(found)}")


def test_repo_self_scan_is_clean_against_baseline():
    """Every future PR is gated on this: the package has no new findings,
    no stale baseline entries, every baseline entry documents WHY it is a
    false positive, and the scan fits the <30s budget."""
    root, files = lint._scan_spec([])
    out = engine.run(root, files)
    assert not out["_new"], "new picolint findings:\n" + "\n".join(
        f.render() for f in out["_new"])
    assert not out["_stale"], (
        "stale baseline entries (the finding no longer fires — remove "
        f"them): {out['_stale']}")
    bad = engine.undocumented_entries(out["_baseline"])
    assert not bad, f"baseline entries without a documented reason: {bad}"
    assert out["elapsed_s"] < 30


def test_cli_default_scan_exits_zero():
    """`python -m picotron_tpu.tools.lint` — the `make lint` contract."""
    assert lint.main(["--json"]) == 0


def test_rule_catalog_is_stable():
    """Rule IDs are API (baselines, suppressions, docs cross-links):
    removing or renaming one breaks every consumer."""
    assert set(RULES) == {
        "PICO-J001", "PICO-J002", "PICO-J003", "PICO-J004", "PICO-J005",
        "PICO-J006",
        "PICO-C001", "PICO-C002", "PICO-C003", "PICO-C004"}
    for rule in RULES.values():
        assert rule.title and rule.rationale


# --------------------------------------------------------------------------- #
# fleet-controller thread fixture (ISSUE 17): the tools/fleet.py locking
# discipline — leaf ``_mu`` for worker STATE only, every scrape/launch
# I/O outside it — modeled as a lint fixture so the discipline that keeps
# ``make lint`` clean with an empty baseline is itself pinned by a test.
# --------------------------------------------------------------------------- #

_FLEET_CLEAN = """
    import threading
    import time

    class Controller:
        def __init__(self):
            self._mu = threading.Lock()
            self._workers = {}
            self._stop = threading.Event()
            self._thread = None

        def start(self):
            self._thread = threading.Thread(target=self._run, daemon=True)
            self._thread.start()

        def _run(self):
            while not self._stop.wait(0.05):
                self.tick()

        def _scrape(self, name):
            time.sleep(0.01)  # stands in for the HTTP metrics scrape
            return {"queue_depth": 0.0}

        def tick(self):
            with self._mu:
                names = list(self._workers)
            scrapes = {n: self._scrape(n) for n in names}
            with self._mu:
                for n, s in scrapes.items():
                    if n in self._workers:
                        self._workers[n] = s

        def stop(self):
            self._stop.set()
            t = self._thread
            if t is not None:
                t.join(timeout=5)
    """


def test_fleet_controller_thread_pattern_scans_clean(tmp_path):
    """The controller idiom — snapshot names under ``_mu``, scrape with
    the lock RELEASED, re-take it to apply — produces zero findings: the
    pattern tools/fleet.py ships with an empty baseline."""
    assert _scan(tmp_path, _FLEET_CLEAN) == []


def test_fleet_controller_scrape_under_lock_is_caught(tmp_path):
    """The tempting shortcut — scraping each worker while still holding
    ``_mu`` — is exactly the hazard C002's one-hop propagation exists
    for: the tick thread would serialize every HTTP round-trip against
    the admin/stop paths."""
    found = _scan(tmp_path, """
        import threading
        import time

        class Controller:
            def __init__(self):
                self._mu = threading.Lock()
                self._workers = {}

            def _scrape(self, name):
                time.sleep(0.01)  # the HTTP round-trip
                return {"queue_depth": 0.0}

            def tick(self):
                with self._mu:
                    for name in list(self._workers):
                        self._workers[name] = self._scrape(name)
        """)
    assert _rules(found) == ["PICO-C002"]
    assert "_scrape" in found[0].message
