"""The one-token state update as a kernel (kernels/ssm_update.py), Mamba-2's and
Mamba-1's over one walk: the advancing rows' state read once, updated and
written in place, ``y`` reduced from it on the way — against ``mamba_step``'s
and ``mamba1_step``'s arithmetic, which it replaces where ``ssm_update_path``
says so, on the CPU in interpret mode. What the chip's compiler makes of it is
tests/test_chip_compile.py's. No engine is built here; one tiny decode forward
a family is compiled, for the last tests.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

# ``mamba_step``'s sum and ``y`` and the masked ``.at[j].set`` of ``paged_decode_forward``, as the timing tool writes them
from sentio_tpu.eval.ssm_update_timing import xla_selective_update as selective_reference, xla_update as reference
from sentio_tpu.kernels.ssm_update import make_ssm_update_impl, selective_update, ssm_update, ssm_update_path
from sentio_tpu.models.jamba import JambaConfig, init_jamba
from sentio_tpu.models.nemotron_h import NemotronHConfig, init_nemotron_h
from sentio_tpu.runtime.paged import init_pool, paged_decode_forward

LM, ROWS, HEADS, P, N, GROUPS = 2, 4, 4, 8, 128, 2


def step_inputs(seed: int, rows=ROWS, heads=HEADS, p=P, n=N, groups=GROUPS):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.uniform(0.5, 1.0, (rows, heads)), jnp.float32),
            jnp.asarray(rng.standard_normal((rows, heads, p)), jnp.float32),
            jnp.asarray(rng.standard_normal((rows, groups, n)), jnp.float32),
            jnp.asarray(rng.standard_normal((rows, groups, n)), jnp.float32))


def close(got, want):
    """To float32 rounding: relative 1e-6 of the array's largest."""
    got, want = np.asarray(got), np.asarray(want)
    return np.abs(got - want).max() <= 1e-6 * max(np.abs(want).max(), 1e-30)


MASKS = {"all-rows": [True] * 4, "one-row": [False, False, True, False], "all-halted": [False] * 4}


N1, INNER1 = 16, 256     # Mamba-1: two tiles of sublanes as the published 16 x 5120 has, two of lanes


def selective_inputs(seed: int, rows=ROWS, n=N1, inner=INNER1):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.uniform(0.001, 0.1, (rows, inner)), jnp.float32),          # D, after the softplus
            jnp.asarray(rng.standard_normal((rows, inner)), jnp.float32),              # D x
            jnp.asarray(rng.standard_normal((rows, n)), jnp.float32),
            jnp.asarray(rng.standard_normal((rows, n)), jnp.float32))


A_LOG = jnp.asarray(np.log(np.random.default_rng(11).uniform(0.5, 16.0, (LM, N1, INNER1))), jnp.float32)   # a layer its own
# a recurrence: the state's shape, the kernel, what it is held to, y's shape a row, (step, layer) → a call's terms
CHAINED = {
    "mamba2": ((LM, ROWS, HEADS, P, N), ssm_update, reference, (HEADS, P), lambda step, layer: step_inputs(step)),
    "mamba1": ((LM, ROWS, N1, INNER1), selective_update, selective_reference, (INNER1,),
               lambda step, layer: (*selective_inputs(step), A_LOG[layer])),
}


@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("recurrence", sorted(CHAINED))
def test_sixteen_chained_steps_are_the_models_steps(recurrence, mask):
    """Sixteen updates in a row, each over the state the one before left, the
    layers in turn (TRACED, as a decode program hands them over; Mamba-1's
    ``a_log`` a layer's own): the new state and ``y`` of the advancing rows to
    float32 rounding; a row that does not advance keeps its state TO THE BIT
    and reads ``y`` zero; the other layer of a call as it was, to the bit;
    the state float32 throughout."""
    shape, kernel, held_to, y_shape, terms = CHAINED[recurrence]
    advancing = jnp.asarray(MASKS[mask])
    start = jnp.asarray(np.random.default_rng(7).standard_normal(shape), jnp.float32)
    update = jax.jit(functools.partial(kernel, interpret=True))
    got = want = start
    for step in range(16):
        layer = step % LM
        inputs = terms(step, layer)
        before = got
        got, y = update(got, jnp.int32(layer), advancing, *inputs)
        want, y_want = held_to(want, layer, advancing, *inputs)
        assert got.dtype == jnp.float32 and got.shape == shape and y.shape == (ROWS, *y_shape)
        assert np.array_equal(np.asarray(got[1 - layer]), np.asarray(before[1 - layer]))
        halted = ~np.asarray(advancing)
        assert np.array_equal(np.asarray(got[layer])[halted], np.asarray(before[layer])[halted])
        assert not np.asarray(y)[halted].any()
        if not halted.all():
            assert close(np.asarray(y)[~halted], np.asarray(y_want)[~halted])
    assert close(got, want)
    if not any(MASKS[mask]):
        assert np.array_equal(np.asarray(got), np.asarray(start))


@pytest.mark.parametrize("p, n", [(64, 128), (24, 256)], ids=["the-cells-p64-n128", "p24-n256"])
def test_heads_of_several_tiles_eight_to_a_group(p, n):
    """A head's ``[P, N]`` of several tiles, eight heads to a group as the
    cell has them, two blocks of heads a row: ``y`` comes back a head a row."""
    heads, groups = 16, 2
    rng = np.random.default_rng(p)
    state = jnp.asarray(rng.standard_normal((1, 2, heads, p, n)), jnp.float32)
    inputs = step_inputs(p, rows=2, heads=heads, p=p, n=n, groups=groups)
    advancing = jnp.asarray([False, True])
    got, y = ssm_update(state, 0, advancing, *inputs, interpret=True)
    want, y_want = reference(state, 0, advancing, *inputs)
    assert close(got, want) and close(y[1], y_want[1]) and not np.asarray(y[0]).any()
    assert np.array_equal(np.asarray(got[0, 0]), np.asarray(state[0, 0]))


CELL = (6, 16, 64, 64, 128)   # ``nemotron3-ep2-chat-closed-16``: six Mamba blocks, 16 slots
XLA_KEPT = {
    "rehearsal-widths": lambda: (jax.ShapeDtypeStruct((3, 2, 8, 8, 16), jnp.float32), None),
    "bf16": lambda: (jax.ShapeDtypeStruct(CELL, jnp.bfloat16), None),
    "mesh": lambda: (jax.ShapeDtypeStruct(CELL, jnp.float32), Mesh(np.array(jax.devices()[:1]), ("tp",))),
    "half-a-tile-of-sublanes": lambda: (jax.ShapeDtypeStruct((6, 16, 64, 4, 128), jnp.float32), None),
    "half-a-row-of-lanes": lambda: (jax.ShapeDtypeStruct((6, 16, 64, 64, 64), jnp.float32), None),
    "a-head-over-one-dma": lambda: (jax.ShapeDtypeStruct((2, 2, 4, 512, 512), jnp.float32), None),
}


@pytest.mark.parametrize("what", sorted(XLA_KEPT))
def test_the_rule_keeps_the_xla_form_for(what):
    assert ssm_update_path(jax.ShapeDtypeStruct(CELL, jnp.float32)) == "pallas"
    assert ssm_update_path(jax.ShapeDtypeStruct((LM, ROWS, HEADS, P, N), jnp.float32)) == "pallas"
    state, mesh = XLA_KEPT[what]()
    assert ssm_update_path(state, mesh) == "xla"
    if mesh is None:
        with pytest.raises(ValueError, match="ssm update: a state of"):
            ssm_update(jnp.zeros(state.shape[:1] + (1,) + state.shape[2:], state.dtype), 0, jnp.ones((1,), bool),
                       *step_inputs(0, 1, state.shape[2], state.shape[3], state.shape[4], 1), interpret=True)


def test_the_rule_on_the_configurations_the_repo_serves():
    """The cell's state as ``init_pool`` makes it takes the kernel; the tiny
    configuration every CPU engine test serves keeps the XLA form."""
    cell = NemotronHConfig(n_layers=14, pattern="MEMEM*EMEMEM*E")
    assert cell.state_shapes(16)["ssm"] == (CELL, jnp.float32)
    assert ssm_update_path(jax.ShapeDtypeStruct(*cell.state_shapes(16)["ssm"])) == "pallas"
    tiny = NemotronHConfig.tiny()
    assert ssm_update_path(jax.ShapeDtypeStruct(*tiny.state_shapes(2)["ssm"])) == "xla"


# ------------------------------------------------- Mamba-1: a decay a channel and state column (PR 49)

def test_a_row_of_several_passes_and_one_tile_of_sublanes():
    """``inner`` of more than one pass of the arithmetic (1,280 lanes: five
    passes of 256 where the published 5,120 takes five of 1,024) over ONE tile
    of sublanes (``N`` 8), the second of two rows advancing."""
    n, inner = 8, 1280
    rng = np.random.default_rng(5)
    state = jnp.asarray(rng.standard_normal((1, 2, n, inner)), jnp.float32)
    a_log = jnp.asarray(np.log(rng.uniform(0.5, 16.0, (n, inner))), jnp.float32)
    inputs = selective_inputs(5, rows=2, n=n, inner=inner)
    advancing = jnp.asarray([False, True])
    got, y = selective_update(state, 0, advancing, *inputs, a_log, interpret=True)
    want, y_want = selective_reference(state, 0, advancing, *inputs, a_log)
    assert close(got, want) and close(y[1], y_want[1]) and not np.asarray(y[0]).any()
    assert np.array_equal(np.asarray(got[0, 0]), np.asarray(state[0, 0]))


PUBLISHED1 = (26, 8, 16, 5120)   # ``jamba2-3b-rag-long``: 26 Mamba-1 layers, 8 slots
SELECTIVE_XLA_KEPT = {
    "rehearsal-widths": lambda: (jax.ShapeDtypeStruct((3, 2, 8, 64), jnp.float32), None),
    "bf16": lambda: (jax.ShapeDtypeStruct(PUBLISHED1, jnp.bfloat16), None),
    "mesh": lambda: (jax.ShapeDtypeStruct(PUBLISHED1, jnp.float32), Mesh(np.array(jax.devices()[:1]), ("tp",))),
    "half-a-tile-of-sublanes": lambda: (jax.ShapeDtypeStruct((26, 8, 4, 5120), jnp.float32), None),
    "a-row-over-one-dma": lambda: (jax.ShapeDtypeStruct((2, 2, 16, 16384), jnp.float32), None),
    "a-state-of-rank-3": lambda: (jax.ShapeDtypeStruct((8, 16, 5120), jnp.float32), None),
}


@pytest.mark.parametrize("what", sorted(SELECTIVE_XLA_KEPT))
def test_the_rule_keeps_the_xla_form_of_a_selective_state_for(what):
    assert ssm_update_path(jax.ShapeDtypeStruct(PUBLISHED1, jnp.float32)) == "pallas"
    state, mesh = SELECTIVE_XLA_KEPT[what]()
    assert ssm_update_path(state, mesh) == "xla"
    if mesh is None and len(state.shape) == 4:
        with pytest.raises(ValueError, match="ssm update: a state of"):
            selective_update(jnp.zeros(state.shape[:1] + (1,) + state.shape[2:], state.dtype), 0, jnp.ones((1,), bool),
                             *selective_inputs(0, 1, *state.shape[2:]), jnp.zeros(state.shape[2:]), interpret=True)


def test_the_rule_on_the_selective_configurations_the_repo_serves_and_each_kernel_refuses_the_others_state():
    """The cell's state as ``init_pool`` makes it (the published widths at 8
    slots) takes the kernel. ``JambaConfig.tiny()``'s ``[8, 128]`` a row is
    exactly ONE float32 tile, so the rule — which reads the operand and no
    name — says ``pallas`` of it too; a CPU engine still keeps the XLA form
    unless the kernels are asked for (``use_pallas``), tests/test_jamba.py.
    A rank the other recurrence owns is refused by name, not misread."""
    cell = JambaConfig()
    assert cell.state_shapes(8)["ssm"] == (PUBLISHED1, jnp.float32)
    assert ssm_update_path(jax.ShapeDtypeStruct(*cell.state_shapes(8)["ssm"])) == "pallas"
    assert JambaConfig.tiny().state_shapes(2)["ssm"] == ((3, 2, 8, 128), jnp.float32)
    assert ssm_update_path(jax.ShapeDtypeStruct(*JambaConfig.tiny().state_shapes(2)["ssm"])) == "pallas"
    with pytest.raises(ValueError, match="rank 4 is selective_update's"):
        ssm_update(jnp.zeros((1, 1, 8, 128)), 0, jnp.ones((1,), bool), *step_inputs(0, 1, 1, 8, 128, 1), interpret=True)
    with pytest.raises(ValueError, match="rank 5 is ssm_update's"):
        selective_update(jnp.zeros((1, 1, 1, 8, 128)), 0, jnp.ones((1,), bool), *selective_inputs(0, 1, 8, 128),
                         jnp.zeros((8, 128)), interpret=True)


STEPPED = {   # a tiny configuration whose state is whole tiles, its seeded tree, the state's shape at 3 slots
    "nemotron": (dataclasses.replace(NemotronHConfig.tiny(), ssm_state=128, dtype="float32"), init_nemotron_h, 5),
    "jamba": (dataclasses.replace(JambaConfig.tiny(), dtype="float32"), init_jamba, 4),
}


@pytest.mark.parametrize("family", sorted(STEPPED))
def test_a_decode_step_with_the_kernel_is_the_step_without(family):
    """``paged_decode_forward`` over a Mamba family at a tiny width whose state is whole tiles, two
    steps in a row (the second reads what the first wrote), one row halted:
    the logits and the advancing rows' state to float32 rounding, the halted
    row's state to the bit, the state float32 as it was. (A float32 model: in
    bf16 one last bit of ``y`` is a rounding step of the residual stream.)"""
    cfg, init, rank = STEPPED[family]
    tree = init(jax.random.PRNGKey(0), cfg)
    pool = init_pool(cfg, num_pages=8, page_size=16, slots=3, snapshots=1)
    assert pool.conv["ssm"].ndim == rank and ssm_update_path(pool.conv["ssm"]) == "pallas"
    table = jnp.asarray([[1, 2], [3, 4], [5, 6]], jnp.int32)
    mask = jnp.asarray([True, False, True])
    rng = np.random.default_rng(3)
    start = {name: jnp.asarray(rng.standard_normal(s.shape), s.dtype) for name, s in pool.conv.items()}

    def two_steps(ssm_impl):   # ONE compile a side: both steps run the one program
        step = jax.jit(functools.partial(paged_decode_forward, return_routed=True, ssm_impl=ssm_impl),
                       static_argnums=1)
        k_pages, v_pages, state = pool.k, pool.v, start
        out = []
        for tok, lens in (([3, 4, 5], [5, 15, 6]), ([6, 7, 8], [6, 15, 7])):
            logits, k_pages, v_pages, _routed, state, _ = step(
                tree, cfg, jnp.asarray(tok), jnp.asarray(lens), table, k_pages, v_pages, write_mask=mask, conv=state)
            out.append(logits)
        return out, state

    (got_logits, got), (want_logits, want) = two_steps(make_ssm_update_impl(interpret=True)), two_steps(None)
    assert got["ssm"].dtype == want["ssm"].dtype == jnp.float32
    for a, b in zip(got_logits, want_logits):
        assert np.allclose(np.asarray(a)[np.asarray(mask)], np.asarray(b)[np.asarray(mask)], rtol=0, atol=2e-5)
    assert close(got["ssm"], want["ssm"])
    assert np.array_equal(np.asarray(got["ssm"][:, 1]), np.asarray(start["ssm"][:, 1]))
    assert not np.array_equal(np.asarray(got["ssm"][:, 0]), np.asarray(start["ssm"][:, 0]))
    # the convolution's columns keep the XLA ``where``: the halted row's to the bit in every block, the first
    # block's (nothing before it differs) too, the later blocks' as the residual stream that fed them
    assert np.array_equal(np.asarray(got["conv"][:, 1]), np.asarray(start["conv"][:, 1]))
    assert np.array_equal(np.asarray(got["conv"][0]), np.asarray(want["conv"][0]))
    assert close(got["conv"], want["conv"])
