"""Helpers for the benchmark's cell tests: one run at a small size, on the
CPU, with the harness's look for a chip skipped."""
import contextlib

from bench import harness


def run(workload, overrides, seed=2**31 + 11, seconds=0.5):
    return harness.run(workload, seed, seconds, False, require_chip=False,
                       overrides=overrides)


def fresh():
    """Drop every compiled stage, so a patched program function is traced."""
    import jax
    from repro.core.experiments import clear_compile_caches

    clear_compile_caches()
    jax.clear_caches()


@contextlib.contextmanager
def patched(obj, name, make):
    """Replace ``obj.name`` by ``make(original)`` for the block."""
    orig = getattr(obj, name)
    setattr(obj, name, make(orig))
    fresh()
    try:
        yield
    finally:
        setattr(obj, name, orig)
        fresh()


def failed(result) -> bool:
    return not result["correct"] and any(
        c["value"] > c["limit"] for c in result["checks"].values())
