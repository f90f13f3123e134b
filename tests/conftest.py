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



def pytest_configure(config):
    # ``--dist loadfile`` hands files out in collection order, not by their count of tests (xdist's
    # default, ``--no-loadscope-reorder`` its switch: set here, because an option in ``pytest.ini`` would
    # fail a run without xdist): ``tests/benchmark/`` is collected first and holds the files of few,
    # long tests that were otherwise handed out last and made the run's tail (ROADMAP D11)
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


# Suites exercising the paged engine / radix cache / decode service run with
# the runtime sanitizer armed (analysis/sanitizer.py): engine entry points
# assert the single-driver-thread contract, annotated locks record
# ownership, and every tick verifies page-pool conservation + radix
# refcounts. A regression in those invariants fails HERE, on the tick that
# introduced it, instead of as a pool-exhaustion heisenbug later.
_SANITIZED_MODULES = {
    "test_chaos",
    "test_elastic",
    "test_lane_handover",
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


class CacheFreeGreedy:
    """The oracle for "paging is a layout, not a model change": greedy
    decoding with NO cache at all. Every token re-runs the whole sequence
    through the family's cache-free forward and takes the argmax of the last
    real position — no pages, no buckets, no sampler, nothing the engine
    under test could share a fault with. Prompts are tokenised (BOS, no
    truncation) and stopped (EOS dropped, ``stop``; budget, ``length``) the
    way ``ContinuousBatchingEngine`` does it; every sequence is right-padded
    to one ``width`` so the forward compiles once a module.

    Holds ``params`` / ``model_config`` / ``tokenizer`` so a test hands the
    engine under test the very same weights."""

    def __init__(self, model_config=None, params=None, tokenizer=None,
                 rng_seed: int = 0, width: int = 256):
        import jax
        import jax.numpy as jnp

        from sentio_tpu.models.families import family_of
        from sentio_tpu.models.tokenizer import ByteTokenizer
        from sentio_tpu.runtime.weights import load_decoder

        if params is None:
            decoder = load_decoder(model_config=model_config, rng_seed=rng_seed)
            params, model_config = decoder.params, decoder.model_config
        self.params = params
        self.model_config = cfg = model_config
        self.tokenizer = tokenizer or ByteTokenizer(cfg.vocab_size)
        self.width = width
        forward = family_of(cfg).forward

        @jax.jit
        def last_logits(params, ids, n):
            # right padding sits after every real token, so causal attention
            # never reads it; the mask keeps it out of expert capacity
            real = jnp.arange(ids.shape[1])[None, :] < n
            logits, *_ = forward(params, cfg, ids, pad_mask=real)
            return logits[0, n - 1]

        self._last_logits = last_logits

    def logits(self, token_ids) -> "np.ndarray":
        """float32 next-token logits after ``token_ids``."""
        import numpy as np

        n = len(token_ids)
        assert n <= self.width, f"{n} tokens exceed the oracle's width {self.width}"
        ids = np.full((1, self.width), self.tokenizer.pad_id, np.int32)
        ids[0, :n] = token_ids
        return np.asarray(self._last_logits(self.params, ids, np.int32(n)))

    def generate(self, prompts, max_new_tokens: int, temperature: float = 0.0):
        from types import SimpleNamespace

        assert temperature == 0.0, "the oracle is greedy"
        out = []
        for prompt in prompts:
            seq = list(self.tokenizer.encode(prompt, add_bos=True))
            n_prompt, emitted, reason = len(seq), [], "length"
            for _ in range(max_new_tokens):
                tok = int(self.logits(seq).argmax())
                if tok == self.tokenizer.eos_id:
                    reason = "stop"
                    break
                emitted.append(tok)
                seq.append(tok)
            out.append(SimpleNamespace(
                tokens=emitted, text=self.tokenizer.decode(emitted),
                prompt_tokens=n_prompt, finish_reason=reason))
        return out
