"""Test/diagnostic instrumentation: XLA compile counting.

The serving guarantees are pinned by tests, not just measured: batch-
shape bucketing promises a BOUNDED compile cache under arbitrary
request sizes, and the stacked-forest cache promises zero re-stack /
re-upload on repeat predicts. This module gives tests the two probes
those assertions need:

- :class:`CompileWatch` — counts XLA compile requests between enter and
  exit via ``jax.monitoring`` events. A jit cache hit records nothing;
  every fresh trace->lower->compile records at least one event, so
  ``watch.compiles == 0`` is exactly "no new program was built" (a
  persistent-compilation-cache hit still counts as a compile request —
  it is a jit cache miss, which is what bucketing bounds).
- :func:`predict_program_cache_size` — the number of distinct compiled
  forest-traversal programs (re-exported from ops/predict.py).
"""
from __future__ import annotations

from typing import List

# any event under this prefix marks one compile request reaching the
# compilation-cache layer (observed: one fresh jit compile fires 1-3 of
# them; a jit cache hit fires none)
_COMPILE_EVENT_PREFIX = "/jax/compilation_cache/compile_requests"


class CompileWatch:
    """Context manager counting XLA compile requests.

    >>> with CompileWatch() as w:
    ...     booster.predict(X)
    >>> assert w.compiles == 0   # warm path: no fresh programs

    ``compiles`` is the number of compile-request events seen — compare
    against zero (exact) or use as an upper-bound proxy; one logical
    compile can fire a small handful of events, so assert ``== 0`` or
    ``<= bound`` with slack, never an exact nonzero count.
    """

    def __init__(self, name: str = "") -> None:
        self.name = name
        self.compiles = 0
        self.events: List[str] = []
        self._active = False

    def assert_compiles(self, at_most: int = 0) -> None:
        """Assert at most ``at_most`` compile requests were seen,
        failing with the captured event list (the warm-start pin:
        ``w.assert_compiles(0)`` after a second same-shape
        construct+engine-init reads "no new XLA program was built")."""
        if self.compiles > at_most:
            compile_events = [e for e in self.events if
                              e.startswith(_COMPILE_EVENT_PREFIX)]
            raise AssertionError(
                f"CompileWatch{f' {self.name!r}' if self.name else ''}: "
                f"{self.compiles} compile request(s), expected at most "
                f"{at_most}. Events: {compile_events[:10]}")

    def _listener(self, event: str, **kwargs) -> None:
        if not self._active:
            return
        self.events.append(event)
        if event.startswith(_COMPILE_EVENT_PREFIX):
            self.compiles += 1

    def __enter__(self) -> "CompileWatch":
        from jax import monitoring
        monitoring.register_event_listener(self._listener)
        self._active = True
        return self

    def __exit__(self, *exc) -> None:
        # stop counting FIRST, then take only OUR listener off —
        # never clear_event_listeners(), which would wipe listeners we
        # do not own
        self._active = False
        from jax import monitoring
        monitoring.unregister_event_listener(self._listener)


def donation_enabled(config) -> bool:
    """Resolve the ``tpu_donate`` tristate against the live backend.

    Buffer donation (``jax.jit(donate_argnums=...)``) lets XLA update
    the boosting carries in place instead of copying them through
    every dispatch (docs/perf.md "Iteration floor"). "auto" donates on
    the TPU backend only — the profiled ``%copy`` waste lives there
    and CPU tier-1 runs keep copy semantics; "true" forces it on any
    backend (the CPU client honors donation, which is what makes the
    donation-on/off bit-identity tests real); "false" disables it
    everywhere (the off arm of the same tests). Donation and
    the persistent compilation cache combine freely on both backends
    (docs/perf.md "Iteration floor" has the check that showed it)."""
    v = str(getattr(config, "tpu_donate", "auto"))
    if v != "auto":
        return v == "true"
    import jax
    return jax.default_backend() == "tpu"


def donation_guard(fn, site: str):
    """``tpu_debug_checks`` use-after-donate guard for a donating jit.

    A donated buffer is DELETED when its dispatch is issued, so a
    caller that re-reads a stale Python reference gets XLA's generic
    ``RuntimeError: Array has been deleted`` wherever the read happens
    to land — far from the donating call. This wrapper checks every
    argument buffer BEFORE dispatch and fails with the donating site
    named, turning the latent crash into an actionable error. Debug
    path only (one ``is_deleted`` flag read per leaf); the production
    wrappers call the jit directly."""
    import jax

    from . import log

    def guarded(*args):
        for leaf in jax.tree.leaves(args):
            if getattr(leaf, "is_deleted", None) is not None \
                    and leaf.is_deleted():
                log.fatal(
                    f"tpu_debug: use-after-donate at {site} — an "
                    f"argument's buffer was already donated to an "
                    f"earlier dispatch and deleted; re-reading a stale "
                    f"reference is a bug (reassign before reading, or "
                    f"set tpu_donate=false)")
        return fn(*args)

    return guarded


def predict_program_cache_size() -> int:
    """Distinct compiled forest-traversal programs held by this process
    (the quantity batch-shape bucketing bounds)."""
    from ..ops.predict import predict_program_cache_size as _sz
    return _sz()


def ingest_program_cache_size() -> int:
    """Distinct compiled device bin-assignment programs (ops/ingest.py)
    held by this process — fixed-shape chunking promises ONE per
    (chunk_rows, features, bins) family, and a second same-shape
    ``Dataset.construct`` must not add any (test_ingest.py pins both)."""
    from ..ops.ingest import ingest_program_cache_size as _sz
    return _sz()
