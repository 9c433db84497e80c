"""The process's one ``jax.profiler`` control: start, stop, or timed.

The only caller of ``jax.profiler.start_trace`` / ``stop_trace`` in the
tree. Two kinds of caller share it:

- a caller that brackets work of its own: ``start(dir)`` opens a capture
  that lasts until ``stop()`` (the training loop's
  ``logging.profile_start/stop`` window; the benchmark's traced stretch);
- "this process is slow RIGHT NOW, grab a trace", for a live server or
  trainer without restarting it: ``start(dir, seconds)`` arms a daemon
  timer that calls the same ``stop()``; ``install_sigusr2(capture)`` makes
  ``kill -USR2 <pid>`` trigger that (the serve CLI and the train CLI both
  install it) and the serving front end exposes it as ``POST /profilez``.

One capture at a time: a start while one is open reports busy instead of
tripping jax's double-start error. The signal handler only spawns the
worker, nothing slow runs on the signal path.

While a capture is open ``obs.tracing``'s module flag is set, so scoped
spans are also written into the capture as ``TraceAnnotation``s; start
and stop each leave one ``pt.anchor`` there (``SpanTracer.anchor``). A
capture holds the host tracer's events and the device's, and no Python
frames (``python_tracer_level`` 0).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

from picotron_tpu.obs import tracing


class ProfileCapture:
    """Profiler window manager. ``start()``/``stop()`` are safe from any
    thread (and ``start`` from a signal handler via ``request()``).
    ``tracer`` is the span ring whose clock the anchors carry (default:
    the process ring)."""

    def __init__(self, out_dir: str, seconds: float = 5.0, log=None,
                 tracer=None):
        self.out_dir = out_dir
        self.seconds = float(seconds)  # the timed captures' default length
        self._tracer = tracer
        self._mu = threading.Lock()
        self._state = "idle"  # -> "open" -> "closing" -> "idle"
        self._opened = 0  # captures opened: a late timer stops only its own
        self._dir = None
        self._t_start = None
        self._count = 0
        self._log = log

    @property
    def running(self) -> bool:
        with self._mu:
            return self._state != "idle"

    @property
    def captures(self) -> int:
        with self._mu:
            return self._count

    def _say(self, msg: str) -> None:
        if self._log is not None:
            self._log(msg)

    def _ring(self):
        if self._tracer is None:
            from picotron_tpu.obs import GLOBAL_TRACER

            self._tracer = GLOBAL_TRACER
        return self._tracer

    def start(self, out_dir: Optional[str] = None,
              seconds: Optional[float] = None) -> dict:
        """Open one capture: until ``stop()``, or for ``seconds`` when
        given. Returns ``{"ok": True, "dir", "seconds"}`` or ``{"ok":
        False, "error"}`` when one is already open (or jax refuses to
        start a trace)."""
        d = out_dir or self.out_dir
        if seconds is not None:
            seconds = float(seconds)
            if seconds <= 0:
                return {"ok": False,
                        "error": f"seconds must be > 0, got {seconds}"}
        with self._mu:
            if self._state != "idle":
                return {"ok": False, "error": "capture already running"}
            self._state = "open"
            self._opened += 1
            opened = self._opened
        try:
            import jax

            os.makedirs(d, exist_ok=True)
            # the host tracer as it is (TraceAnnotations, so the spans and
            # the anchors); no Python frames: no reader takes one, and
            # tracing every call of the loop it times adds 1.5-3 ms a round
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(d, profiler_options=options)
        except Exception as e:  # noqa: BLE001 - reported, never fatal
            with self._mu:
                self._state = "idle"
            return {"ok": False,
                    "error": f"profiler start failed: {e}"}
        self._dir, self._t_start = d, time.monotonic()
        self._ring().anchor()
        tracing.set_capture_open(True)
        if seconds is not None:
            threading.Thread(target=self._stop_after,
                             args=(seconds, opened),
                             name="obs-profile-stop", daemon=True).start()
        self._say(f"profiler: capturing "
                  f"{'until stopped' if seconds is None else f'{seconds:.3g}s'}"
                  f" into {d}")
        return {"ok": True, "dir": d, "seconds": seconds}

    def stop(self, _opened: Optional[int] = None) -> dict:
        """Close the open capture. Returns ``{"ok": True, "dir",
        "t_start", "t_stop"}`` (``time.monotonic``: the trace covers
        [t_start, t_stop]) or ``{"ok": False, "error"}`` when none is open
        (or jax fails to write the trace)."""
        with self._mu:
            if self._state != "open" or _opened not in (None, self._opened):
                return {"ok": False, "error": "no capture open"}
            self._state = "closing"
        tracing.set_capture_open(False)  # before the seconds stop_trace takes
        out = {"ok": True, "dir": self._dir, "t_start": self._t_start}
        try:
            import jax

            self._ring().anchor()
            out["t_stop"] = time.monotonic()
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 - reported, never fatal
            out = {"ok": False, "error": f"profiler stop failed: {e}"}
            self._say(f"profiler: stop failed: {e}")
        finally:
            with self._mu:
                self._state = "idle"
                self._count += 1
        self._say("profiler: capture done")
        return out

    def _stop_after(self, seconds: float, opened: int) -> None:
        time.sleep(seconds)
        self.stop(opened)  # a no-op when stop() closed it first

    def request(self) -> None:
        """Signal-handler-safe trigger of one timed capture: hand the
        start to a worker thread so the handler never touches jax or the
        filesystem."""
        threading.Thread(target=self.start,
                         kwargs={"seconds": self.seconds},
                         name="obs-profile-start", daemon=True).start()


def install_sigusr2(capture: ProfileCapture) -> bool:
    """SIGUSR2 -> one timed capture. Returns False off the main thread
    (embedded runs: the signal surface is simply unavailable there)."""
    import signal

    try:
        signal.signal(signal.SIGUSR2,
                      lambda signum, frame: capture.request())
        return True
    except ValueError:
        return False
