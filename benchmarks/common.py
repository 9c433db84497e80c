"""What both runners share: the model section of the program's config, the
compile counter, the traced stretch of the window, the benchmark's own host
spans."""

from __future__ import annotations

import os
import shutil
import time

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

MODEL_KEYS = ("num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "hidden_size", "intermediate_size",
              "vocab_size", "rms_norm_eps", "rope_theta",
              "max_position_embeddings")


def model_section(config: dict) -> dict:
    """The published keys ``ModelConfig`` has, by the same names; a key it
    lacks (``sliding_window``, ``head_dim``) is checked, never passed."""
    m = {k: config[k] for k in MODEL_KEYS}
    m["name"] = config["name"]
    m["dtype"] = config.get("torch_dtype", "bfloat16")
    hd = config.get("head_dim")
    if hd and hd != m["hidden_size"] // m["num_attention_heads"]:
        raise SystemExit(f"head_dim {hd} is not hidden_size/heads: "
                         f"ModelConfig cannot express it")
    if config.get("sliding_window"):
        raise SystemExit("ModelConfig has no sliding window")
    return m


class CompileCounter:
    """Counts backend compiles (cache hits included: either means a shape
    the warm-up missed). ``in_window`` is what happened after ``mark()``."""

    def __init__(self):
        import jax.monitoring

        self.total = 0
        self._mark = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.total += 1

    def mark(self) -> None:
        self._mark = self.total

    @property
    def in_window(self) -> int:
        return self.total - self._mark


def span(name: str):
    """A host span on the profiler's clock (a no-op when nothing traces)."""
    import jax

    return jax.profiler.TraceAnnotation("bench:" + name)


class Tracer:
    """Traces one stretch of the window with ``jax.profiler``, reduces it
    and deletes the files: a tree that grows by a trace per run becomes too
    large to copy."""

    def __init__(self, ctx: dict):
        self.dir = os.path.join(ctx["scratch"], "trace",
                                ctx["cell"]["name"])
        self.chips = ctx["chips"]
        self.log = ctx["log"]
        self.debug_dir = ctx["debug_dir"]
        self.t_start = self.t_stop = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        self.t_start = time.perf_counter()

    def stop(self) -> None:
        import jax

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    @property
    def active(self) -> bool:
        return self.t_start is not None and self.t_stop is None

    def reduce(self) -> dict | None:
        from benchmarks import trace_reduce

        if self.t_stop is None:
            return None
        try:
            pd = trace_reduce.load(self.dir)
            if pd is None:
                self.log("trace: no .xplane.pb was written")
                return None
            if self.debug_dir:
                # for reading a new kind of trace by hand (README)
                with open(os.path.join(self.debug_dir,
                                       "trace_description.txt"), "w") as f:
                    f.write(trace_reduce.describe(pd))
            out = trace_reduce.reduce(pd, self.t_stop - self.t_start,
                                      self.chips)
            if out is not None:
                out.update(t_start=self.t_start, t_stop=self.t_stop)
            return out
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
