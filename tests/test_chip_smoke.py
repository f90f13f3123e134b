"""chip_smoke.py off the chip, and what it stands on.

The smoke proves the main path on a TPU; here its control flow is walked on
the CPU, where it must end ``"ok": false`` whatever happened — a CPU run is
never a device result. Also pinned: the compile-cache placement every entry
point shares, the seeded checkpoints' agreement with the repo's own presets
and init trees, and the one-process-per-chip refusal.
"""

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


def _child_env(**over) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env.update(over)
    return env


def test_rehearsal_on_cpu_walks_every_phase_and_ends_not_ok():
    """``JAX_PLATFORMS=cpu python chip_smoke.py``: three server starts, every
    check on every answer — and still a non-zero exit and ``"ok": false``,
    because the platform is not a TPU."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], cwd=str(REPO),
        env=_child_env(JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=600,
    )
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    assert proc.returncode != 0
    assert lines[-1]["ok"] is False and "rehearsal" in lines[-1]["reason"], lines[-1]
    assert '"platform": "tpu"' not in proc.stdout
    phases = [ln.get("phase") for ln in lines]
    assert [p for p in phases if str(p).startswith("serve:")] == [
        "serve:bf16-cold", "serve:int8", "serve:bf16-warm"]
    int8 = lines[phases.index("serve:int8")]
    assert int8["kv_quant"] == "int8" and int8["server_exit_code"] == 0
    assert int8["bm25_backend"] == "native"


def test_no_tpu_stops_before_any_work(monkeypatch, capsys, tmp_path):
    """No TPU and no rehearsal asked for: stop at the probe — no checkpoint
    written, no server started, nothing continued on the CPU."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    monkeypatch.setattr(chip_smoke, "WORK", tmp_path / "work")
    monkeypatch.setattr(chip_smoke, "probe_device", lambda: {
        "platform": "cpu", "kind": "cpu", "count": 1})

    def no_children(*a, **kw):
        raise AssertionError("the smoke went on without a TPU")

    monkeypatch.setattr(chip_smoke, "Server", no_children)
    monkeypatch.setattr(chip_smoke, "write_llama_checkpoint", no_children)

    assert chip_smoke.main() != 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["ok"] is False and "no TPU" in last["reason"]
    assert last["device"]["platform"] == "cpu"


_RESOLVE = ("from sentio_tpu.infra.compile_cache import ensure_compile_cache\n"
            "print(ensure_compile_cache())\n"
            "import jax\n"
            "print(jax.config.jax_compilation_cache_dir)\n")


def _resolve_cache(cwd: Path, **env) -> list[str]:
    proc = subprocess.run(
        [sys.executable, "-c", _RESOLVE], cwd=str(cwd), text=True,
        capture_output=True, timeout=120, check=True,
        env=_child_env(PYTHONPATH=str(REPO), **env),
    )
    return proc.stdout.strip().splitlines()


def test_compile_cache_honours_the_environment(tmp_path):
    """Placed from outside: JAX reads the variable itself, code sets nothing."""
    placed = str(tmp_path / "placed-cache")
    assert _resolve_cache(tmp_path, JAX_COMPILATION_CACHE_DIR=placed) == [placed, placed]


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(tmp_path):
    """Unplaced: two processes in two working directories resolve the SAME
    in-checkout directory — no temp name, pid or timestamp in it."""
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    want = str(REPO / ".jax_compile_cache")
    assert _resolve_cache(tmp_path / "a") == [want, want]
    assert _resolve_cache(tmp_path / "b") == [want, want]


def test_smoke_widths_are_the_repos_own_presets():
    from sentio_tpu.models.llama import LlamaConfig
    from sentio_tpu.models.transformer import EncoderConfig

    assert chip_smoke.LLAMA3_8B == dataclasses.asdict(LlamaConfig.llama3_8b())
    assert chip_smoke.TINY_LLAMA == {
        **dataclasses.asdict(LlamaConfig.tiny()), "max_len": 1024}
    assert chip_smoke.TINY_ENCODER == dataclasses.asdict(EncoderConfig.tiny())
    # bge-reranker-base (BASELINE.json): 12 layers, dim 768, 12 heads, MLP 3072
    base = chip_smoke.RERANKER_BASE
    assert (base["n_layers"], base["dim"], base["n_heads"], base["mlp_dim"]) == (
        12, 768, 12, 3072)
    EncoderConfig(**base)  # every key is a config field


def test_seeded_checkpoints_have_the_init_trees(tmp_path):
    """What the script writes with numpy is, leaf for leaf, the tree the
    repo's own init functions build — and restores through load_model."""
    from sentio_tpu.models.cross_encoder import init_cross_encoder
    from sentio_tpu.models.llama import init_llama
    from sentio_tpu.runtime.weights import load_model

    chip_smoke.write_llama_checkpoint(tmp_path / "llama", chip_smoke.TINY_LLAMA, seed=5)
    chip_smoke.write_reranker_checkpoint(tmp_path / "rr", chip_smoke.TINY_ENCODER, seed=6)
    for path, family, init in ((tmp_path / "llama", "llama", init_llama),
                               (tmp_path / "rr", "cross-encoder", init_cross_encoder)):
        params, cfg, _ = load_model(str(path), expect_family=family)
        want = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))
        assert jax.tree.structure(params) == jax.tree.structure(want)
        assert jax.tree.map(lambda a: a.shape, params) == jax.tree.map(
            lambda a: a.shape, want)
        matrices = [a for a in jax.tree.leaves(params) if a.ndim == 2]
        assert matrices and all(str(a.dtype) == "bfloat16" for a in matrices)


def test_local_worker_processes_are_refused_on_a_tpu(monkeypatch):
    """One process per chip: on a TPU host the router already holds the chip,
    so REPLICA_MODE=process must refuse at start-up with a typed error that
    says why — not hang in worker start-up until warmup_budget_s."""
    from sentio_tpu.config import (
        EmbedderConfig, GeneratorConfig, RerankConfig, ServeConfig, Settings,
    )
    from sentio_tpu.infra.exceptions import DeviceError
    from sentio_tpu.serve.dependencies import DependencyContainer

    settings = Settings(
        embedder=EmbedderConfig(provider="hash", dim=32),
        generator=GeneratorConfig(provider="tpu", model_preset="tiny",
                                  use_verifier=False),
        rerank=RerankConfig(enabled=False),
        serve=ServeConfig(replicas=2, replica_mode="process"),
    )
    container = DependencyContainer(settings=settings)
    assert container.decoder is not None  # loaded on the CPU, as the router would
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(DeviceError, match="one process"):
        container.generation_service
