"""What both runners share: the model section of the program's config, the
compile counter, the reduction of a traced stretch, the benchmark's own host
spans."""

from __future__ import annotations

import os
import shutil

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
    """Where a traced stretch is written and how it becomes numbers. The
    program captures: the runner starts and stops the program's own
    ``picotron_tpu.obs.ProfileCapture`` (the server's, the object ``POST
    /profilez`` uses; one of its own around the train loop) through
    ``open()`` and hands ``stop()``'s answer to ``reduce()``, which
    deletes the files: a tree that grows by a trace per run becomes too
    large to copy."""

    def __init__(self, ctx: dict):
        self.dir = os.path.join(ctx["scratch"], "trace",
                                ctx["cell"]["name"])
        self.chips = ctx["chips"]
        self.log = ctx["log"]
        self.debug_dir = ctx["debug_dir"]

    def open(self, capture) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir)
        started = capture.start(self.dir)
        if not started["ok"]:
            raise SystemExit(f"trace: {started['error']}")

    def warm(self, capture) -> None:
        """One capture started, stopped and thrown away, so that what the
        profiler's first start costs falls into no number."""
        self.open(capture)
        capture.stop()
        shutil.rmtree(self.dir, ignore_errors=True)

    def reduce(self, stopped: dict) -> dict | None:
        """``stopped`` is what ``ProfileCapture.stop()`` returned."""
        from benchmarks import trace_reduce

        try:
            if not stopped["ok"]:
                self.log(f"trace: {stopped['error']}")
                return None
            pd = trace_reduce.load(self.dir)
            if pd is None:
                self.log("trace: no .xplane.pb was written")
                return None
            if self.debug_dir:
                # for reading a new kind of trace by hand (README)
                with open(os.path.join(self.debug_dir,
                                       "trace_description.txt"), "w") as f:
                    f.write(trace_reduce.describe(pd))
            t_start, t_stop = stopped["t_start"], stopped["t_stop"]
            out = trace_reduce.reduce(pd, t_stop - t_start, self.chips)
            if out is not None:
                out.update(t_start=t_start, t_stop=t_stop)
            return out
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
