"""Test harness configuration.

Force JAX onto the host CPU platform with 8 virtual devices BEFORE jax is
imported anywhere — this is how multi-chip sharding (dp/tp/sp meshes,
collectives) is exercised on a single host with no TPU attached, mirroring
the reference's mock-backend test strategy (SURVEY.md §4) at the device
level.
"""

import os

# tests run without a chip: the CPU platform, eight virtual devices
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
# entry points place a persistent compile cache inside the checkout
# (sentio_tpu/infra/compile_cache.py); tests — and the servers and workers
# they spawn — neither read nor write one
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest  # noqa: E402

from sentio_tpu.config import Settings, set_settings  # noqa: E402

# Suites exercising the paged engine / radix cache / decode service run with
# the runtime sanitizer armed (analysis/sanitizer.py): engine entry points
# assert the single-driver-thread contract, annotated locks record
# ownership, and every tick verifies page-pool conservation + radix
# refcounts. A regression in those invariants fails HERE, on the tick that
# introduced it, instead of as a pool-exhaustion heisenbug later.
_SANITIZED_MODULES = {
    "test_chaos",
    "test_elastic",
    "test_paged",
    "test_paged_sched",
    "test_paged_spec",
    "test_phases",
    "test_prefix_cache",
    "test_replica",
    "test_service",
    "test_sanitize",
}


@pytest.fixture(scope="module", autouse=True)
def _sanitize_engine_suites(request):
    # module-scoped (not function-scoped): autouse fixtures instantiate
    # before other fixtures of the same scope, so the env var is set before
    # any module-scoped engine fixture constructs its engine — a
    # function-scoped monkeypatch would arm the sanitizer AFTER those
    # engines were already built with _san=None
    module = getattr(request, "module", None)
    if module is None or module.__name__ not in _SANITIZED_MODULES:
        yield
        return
    prior = os.environ.get("SENTIO_SANITIZE")
    os.environ["SENTIO_SANITIZE"] = "1"
    yield
    if prior is None:
        os.environ.pop("SENTIO_SANITIZE", None)
    else:
        os.environ["SENTIO_SANITIZE"] = prior


@pytest.fixture()
def settings():
    """A fresh default Settings tree pinned as the singleton for the test."""
    s = Settings()
    set_settings(s)
    yield s
    set_settings(None)


@pytest.fixture()
def docs():
    from sentio_tpu.models.document import Document

    corpus = [
        ("d1", "The quick brown fox jumps over the lazy dog."),
        ("d2", "TPUs accelerate matrix multiplication with a systolic array."),
        ("d3", "JAX composes function transformations like jit grad and vmap."),
        ("d4", "The dog sleeps while the fox runs through the forest."),
        ("d5", "Retrieval augmented generation combines search with language models."),
        ("d6", "BM25 is a ranking function used by search engines for scoring."),
        ("d7", "Flash attention tiles the softmax computation to save memory bandwidth."),
        ("d8", "A lazy dog and a quick fox are common in typing exercises."),
    ]
    return [Document(text=t, id=i, metadata={"source": f"{i}.txt"}) for i, t in corpus]
