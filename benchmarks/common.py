"""What both runners share: the model section of the program's config, the
reference a configuration names, the compile counter, the reduction of a
traced stretch, the benchmark's own host spans."""

from __future__ import annotations

import dataclasses
import importlib.util
import os
import shutil
import sys

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

MODEL_KEYS = ("num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "hidden_size", "intermediate_size",
              "vocab_size", "rms_norm_eps", "rope_theta",
              "max_position_embeddings")
DEFAULT_REFERENCE = "dense_decoder"
HERE = os.path.dirname(os.path.abspath(__file__))


def refuse(message: str):
    """A configuration this program or this benchmark cannot run: a message
    and exit code 2 at once, before any device work, so that a commit that
    lacks what a later configuration asks for fails quickly and never
    hangs."""
    print("run.py: " + message, file=sys.stderr, flush=True)
    raise SystemExit(2)


def load_file(kind: str, name: str):
    """The module ``benchmarks/<kind>/<name>.py``: what belongs to one metric
    or one configuration is a file found by its name."""
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name.replace('.', '_').replace('-', '_')}",
        os.path.join(HERE, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_section(config: dict) -> dict:
    """What the program's ``ModelConfig`` is given: ``MODEL_KEYS`` and, after
    them, the published keys the configuration lists under ``model_keys``,
    all by the same names. A listed key ``ModelConfig`` has no field for is
    refused by name. ``head_dim`` and ``sliding_window`` are checked, never
    passed, unless listed."""
    from picotron_tpu.config import ModelConfig

    listed = [k for k in config.get("model_keys", ()) if k not in MODEL_KEYS]
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    for k in listed:
        if k not in fields:
            refuse(f"configuration {config['name']!r} lists model key "
                   f"{k!r}: ModelConfig cannot express it")
        if k not in config:
            refuse(f"configuration {config['name']!r} lists model key "
                   f"{k!r} and gives it no value")
    m = {k: config[k] for k in (*MODEL_KEYS, *listed)}
    m["name"] = config["name"]
    m["dtype"] = config.get("torch_dtype", "bfloat16")
    hd = config.get("head_dim")
    if "head_dim" not in listed and hd \
            and hd != m["hidden_size"] // m["num_attention_heads"]:
        refuse(f"configuration {config['name']!r}: head_dim {hd} is not "
               f"hidden_size/heads: ModelConfig cannot express it")
    if "sliding_window" not in listed and config.get("sliding_window"):
        refuse(f"configuration {config['name']!r}: ModelConfig has no "
               f"sliding window")
    return m


def load_reference(config: dict):
    """The plain reference the configuration names under ``reference``
    (absent: ``dense_decoder``): the module ``benchmarks/reference/<name>.py``
    with ``forward_logits(params, tokens, config, device)`` and
    ``loss(params, ids, targets, config, device)`` (README: the contract)."""
    name = config.get("reference", DEFAULT_REFERENCE)
    if not os.path.isfile(os.path.join(HERE, "reference", f"{name}.py")):
        refuse(f"configuration {config['name']!r} names the reference "
               f"{name!r}: there is no benchmarks/reference/{name}.py")
    mod = load_file("reference", name)
    for fn in ("forward_logits", "loss"):
        if not callable(getattr(mod, fn, None)):
            refuse(f"reference {name!r} has no function {fn}()")
    return mod


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
