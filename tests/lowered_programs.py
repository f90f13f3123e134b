"""Compare the lowered text of the serving programs of two trees, line by line
(PR 47's gate: a refactor of the seam between ``models/`` and ``runtime/`` keeps
the programs' text). Not a test: run by hand, from the tree under change.

  mkdir .parent && git archive <parent> | tar -x -C .parent
  python tests/lowered_programs.py fixtures $PWD/.parent $PWD

It dumps each tree in a process of its own, one after the other, into a
directory it makes for this run under ``$TMPDIR`` (``tempfile.mkdtemp``), names
the programs that differ, leaves their unified diffs there and exits 1; where
all are identical it removes the directory and exits 0. Both trees are read
through ONE path, a link inside that directory which each dump points at its
tree: a Pallas call's serialized body carries its source's file name. Two
checkouts on one machine share nothing.

``fixtures``: the programs of ``tests/test_chip_compile.py``'s fixtures (decode step
and prior prefill of the benchmark's eight configurations at its widths), lowered
for a described v5e, nothing compiled (~100 s a tree). ``engines``: every jit
family a tiny engine of each registered family dispatches on the CPU — whole
and chunked admission, a radix hit, with and without the kernels in interpret
mode — and the tokens it served (~10 min a tree).
"""
import difflib, hashlib, importlib.util, os, pathlib, shutil, subprocess, sys, tempfile

if sys.argv[1] != "--dump":
    what, trees = sys.argv[1], dict(zip(("parent", "change"), sys.argv[2:4]))
    work = pathlib.Path(tempfile.mkdtemp(prefix="lowered-"))
    link = work / "tree"
    for side, tree in trees.items():
        if link.is_symlink():
            link.unlink()
        link.symlink_to(pathlib.Path(tree).resolve())
        with open(work / f"{side}.log", "w") as log:
            subprocess.run([sys.executable, __file__, "--dump", what, str(link), str(work / side)],
                           stdout=log, stderr=subprocess.STDOUT, check=True)
    names = sorted({f.name for side in trees for f in (work / side).iterdir()})
    differ = []
    for name in names:
        texts = [(work / side / name).read_text() if (work / side / name).exists() else "" for side in trees]
        if texts[0] != texts[1]:
            differ.append(name)
            (work / f"{name}.diff").write_text("".join(difflib.unified_diff(
                *(t.splitlines(keepends=True) for t in texts), *(f"{side}/{name}" for side in trees), n=1)))
    print(f"{what}: {len(names) - len(differ)} of {len(names)} programs identical")
    if differ:
        print("differ:", *differ, f"(dumps, logs and diffs in {work})", sep="\n  ")
    else:
        shutil.rmtree(work)
    sys.exit(1 if differ else 0)

what, root, out = sys.argv[2], sys.argv[3], pathlib.Path(sys.argv[4])
os.chdir(root)
sys.path.insert(0, root)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
out.mkdir(parents=True, exist_ok=True)
import jax

jax.config.update("jax_enable_compilation_cache", False)
jax.config.update("jax_traceback_in_locations_limit", 0)   # no Python frames in an operation's location


def put(name, text):
    (out / f"{name}.txt").write_text(text)
    print(name, len(text), hashlib.sha256(text.encode()).hexdigest()[:16], flush=True)


if what == "fixtures":
    from jax import stages

    seen = []

    class Stub:
        def as_text(self):
            return ""

        def __getattr__(self, name):
            return lambda *a, **k: None

    def compile_(self, *a, **k):
        seen.append(self.as_text())
        return Stub()

    stages.Lowered.compile = compile_
    spec = importlib.util.spec_from_file_location("tcc", f"{root}/tests/test_chip_compile.py")
    tcc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tcc)

    def raw(fix):
        for attr in ("_fixture_function", "__wrapped__", "__pytest_wrapped__"):
            f = getattr(fix, attr, None)
            if f is not None:
                return getattr(f, "obj", f)
        return fix

    gen = raw(tcc.v5e)()
    topo = next(gen)
    for width in sorted(tcc.WIDTHS):
        for tp in (1, 4):
            seen.clear()
            tcc._decoder_programs(topo, width, True, tp)
            for name, text in zip(("step", "prefill"), seen):
                put(f"decoder-{width}-tp{tp}-{name}", text)
    for fam in ("commanda", "deepseek", "lfm2", "nemotron", "jamba", "mellum"):
        if not hasattr(tcc, f"{fam}_programs"):   # a parent tree from before the family came
            continue
        seen.clear()
        res = raw(getattr(tcc, f"{fam}_programs"))(topo)
        texts = res[-1] if isinstance(res[-1], dict) else {}
        for i, text in enumerate(seen):
            put(f"{fam}-{i}", text)
        for name, text in texts.items():
            if name.endswith(".lowered"):
                put(f"{fam}-{name}", text)
else:
    from sentio_tpu.analysis.audit import registry
    from sentio_tpu.runtime.paged import ContinuousBatchingEngine
    from sentio_tpu.runtime.weights import load_decoder

    texts = {}
    tag = [""]
    call = registry.FamilyFn.__call__

    def traced(self, *args, **kwargs):
        key = f"{tag[0]}-{self.family}-{hashlib.sha256(registry.abstract_signature(args, kwargs).encode()).hexdigest()[:8]}"
        if key not in texts:
            texts[key] = self._fn.lower(*args, **kwargs).as_text()
        return call(self, *args, **kwargs)

    registry.FamilyFn.__call__ = traced
    from sentio_tpu.models.cohere2_moe import Cohere2MoeConfig
    from sentio_tpu.models.deepseek_v2 import DeepseekV2Config
    from sentio_tpu.models.lfm2_moe import Lfm2MoeConfig
    from sentio_tpu.models.llama import LlamaConfig
    from sentio_tpu.models.moe import MoeConfig
    from sentio_tpu.models.nemotron_h import NemotronHConfig

    head = "the quick brown fox jumps over the lazy dog and runs far away. "
    prompts = [head + "what then?", head + "and what of the dog, who slept on through all of it?", "short"]
    for cfg in (LlamaConfig.tiny(), MoeConfig.tiny(), Cohere2MoeConfig.tiny(), DeepseekV2Config.tiny(),
                Lfm2MoeConfig.tiny(), NemotronHConfig.tiny()):
        params = load_decoder(model_config=cfg).params
        for pallas in (None, True):
            for chunk in (None, 32):
                tag[0] = f"{type(cfg).__name__}-pallas{pallas}-chunk{chunk}"
                try:
                    eng = ContinuousBatchingEngine(model_config=cfg, params=params, max_slots=4, page_size=16, max_pages_per_seq=12,
                                                   steps_per_tick=4, use_pallas=pallas, prefill_chunk=chunk,
                                                   ignore_eos=True)
                    res = eng.run_all(prompts[:2], max_new_tokens=6)
                    res += eng.run_all(prompts[1:], max_new_tokens=6, **({"return_choices": True} if eng.routed else {}))
                    put(f"{tag[0]}-tokens", repr([r.tokens for r in res]))
                except Exception as exc:  # noqa: BLE001
                    put(f"{tag[0]}-tokens", f"FAILED {type(exc).__name__} {exc}")
    for key, text in sorted(texts.items()):
        put(key, text)
