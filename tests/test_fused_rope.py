"""The one rotary of the package (``fused_rope._apply_rope``: ``t * C + (t @
P) * S``) against the split / negate / concatenate form, kept here as plain
``jax.numpy``; the model file's ``_rotary`` over it; and what the v5e's
compiler makes of it at the two expert cells' shapes (compile only)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.transformer.functional import fused_rope
from apex_tpu.transformer.functional.fused_rope import _apply_rope, _half_swap
from apex_tpu.transformer.testing.standalone_transformer_lm import _rotary

F32 = jnp.float32
# (d, first, d2): the whole 128-lane head; Apex's pass-through (the first 64
# of 192 rotate); latent attention (the last 64 of 192 rotate)
LANES = {"whole": (128, 0, 128), "apex": (192, 0, 64),
         "latent": (192, 128, 64)}


def _split_rope(t, cos, sin, first):
    """The plain reference: slice the rotary lanes, split them in halves,
    negate, concatenate, multiply-add in float32, join the rest back."""
    d2 = cos.shape[-1]
    r = t[..., first:first + d2].astype(F32)
    x1, x2 = jnp.split(r, 2, axis=-1)
    out = (r * cos + jnp.concatenate([-x2, x1], axis=-1) * sin).astype(t.dtype)
    return jnp.concatenate([t[..., :first], out, t[..., first + d2:]], axis=-1)


def _case(lanes, dtype, layout, seed=0):
    d, first, d2 = LANES[lanes]
    s, b, n = 24, 2, 3
    kt, kf, kg = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape, table = (((s, b, n, d), (s, 1, 1, d2)) if layout == "sbhd"
                    else ((b, n, s, d), (s, d2)))
    t = jax.random.normal(kt, shape, F32).astype(dtype)
    freqs = jax.random.uniform(kf, table, F32, 0.0, 6.0)
    g = jax.random.normal(kg, shape, F32)           # the loss's weights
    return t, jnp.cos(freqs), jnp.sin(freqs), first, g


def _close(got, want, dtype, what, terms=0.0):
    """One bfloat16 rounding apart (an ulp, 2^-7 of a binade's lowest
    value, of the larger of the two and of ``terms``), or 1e-6 in
    float32."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if dtype == jnp.bfloat16:
        tol = 2.0 ** -7 * (np.maximum(np.abs(got), np.abs(want)) + terms)
        tol = tol + 1e-30
    else:
        tol = 1e-6 * (1.0 + np.abs(want))
    worst = np.max(np.abs(got - want) - tol)
    assert worst <= 0, f"{what}: {worst} over the tolerance"


CASES = [(lanes, dtype, layout) for lanes in LANES
         for dtype in (jnp.bfloat16, jnp.float32)
         for layout in ("sbhd", "bnsd")]
IDS = [f"{lanes}-{jnp.dtype(dt).name}-{layout}" for lanes, dt, layout in CASES]


@pytest.mark.parametrize("lanes,dtype,layout", CASES, ids=IDS)
def test_the_product_form_gives_the_split_form_s_values(lanes, dtype, layout):
    t, cos, sin, first, _ = _case(lanes, dtype, layout)
    got = jax.jit(_apply_rope, static_argnums=3)(t, cos, sin, first)
    want = _split_rope(t, cos, sin, first)
    assert got.dtype == t.dtype and got.shape == t.shape
    _close(got, want, dtype, "values")
    d, _, d2 = LANES[lanes]
    through = np.r_[0:first, first + d2:d]
    np.testing.assert_array_equal(                  # untouched lanes: exact
        np.asarray(got.astype(F32))[..., through],
        np.asarray(t.astype(F32))[..., through])


@pytest.mark.parametrize("lanes,dtype,layout", CASES, ids=IDS)
def test_the_product_form_gives_the_split_form_s_gradients(lanes, dtype,
                                                           layout):
    """With respect to ``t``, ``cos`` and ``sin``: autodiff's, no
    hand-written VJP (``dt = g * C + (g * S) @ P^T``). ``t`` has two uses
    and each hands back its cotangent in ``t``'s dtype, so of a bfloat16
    ``t`` the two terms (each at most the row's largest ``|g|``) are
    rounded before they are added: a pair of conversions the chip's
    compiler elides (``PERF.md`` §6, PR 36: the split form's ``dt`` bit
    for bit) and the CPU keeps."""
    t, cos, sin, first, g = _case(lanes, dtype, layout, seed=1)

    def loss(fn):
        return lambda t, c, s: jnp.sum(fn(t, c, s, first).astype(F32) * g)

    got = jax.jit(jax.grad(loss(_apply_rope), (0, 1, 2)))(t, cos, sin)
    want = jax.grad(loss(_split_rope), (0, 1, 2))(t, cos, sin)
    assert got[0].dtype == t.dtype
    _close(got[0], want[0], dtype, "dt",
           terms=np.max(np.abs(np.asarray(g)), axis=-1, keepdims=True))
    for a, b, what in zip(got[1:], want[1:], ("dcos", "dsin")):
        assert a.shape == b.shape and a.dtype == F32
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-5, err_msg=what)


@pytest.mark.parametrize("lanes", list(LANES))
def test_the_half_swap_is_a_signed_permutation_of_the_rotary_lanes(lanes):
    d, first, d2 = LANES[lanes]
    p = _half_swap(d, first, d2)
    assert p.shape == (d, d) and set(np.unique(p)) <= {-1.0, 0.0, 1.0}
    rotary = np.zeros(d)
    rotary[first:first + d2] = 1.0
    np.testing.assert_array_equal(p.T @ p, np.diag(rotary))
    np.testing.assert_array_equal(p @ p, -np.diag(rotary))  # two swaps: -t
    x = np.arange(1.0, d + 1.0)
    want = np.zeros(d)
    want[first:first + d2 // 2] = -x[first + d2 // 2:first + d2]
    want[first + d2 // 2:first + d2] = x[first:first + d2 // 2]
    np.testing.assert_array_equal(x @ p, want)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16, jnp.float32])
def test_the_swap_is_asked_at_the_highest_precision(dtype):
    """A default-precision product on the chip rounds a float32 operand to
    bfloat16: the forward product of a wider row and, for every row, the
    backward pass's float32 ``g * S`` would lose bits."""
    t = jnp.zeros((2, 4, 8), dtype)
    table = jnp.ones((4, 8), F32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda t: jnp.sum(_apply_rope(t, table, table).astype(F32))))(t)
    dots = [e.params for e in jaxpr.eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 2                       # the swap, and its transpose
    for params in dots:
        assert "HIGHEST" in str(params["precision"]).upper(), params
        assert params["preferred_element_type"] == F32


def test_no_lane_of_the_row_is_sliced_split_or_joined():
    """The traced function holds one product and no ``slice``,
    ``concatenate`` or ``neg`` of anything as large as the row (the
    tables' ``pad`` is the only lane surgery); the package has no
    ``_rotate_half``."""
    t = jnp.zeros((2, 3, 16, 192), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda t: _rotary(t, 1e4, 128))(t)
    for e in jaxpr.eqns:
        if e.primitive.name in ("slice", "concatenate", "neg", "pad",
                                "dynamic_slice", "split"):
            assert all(v.aval.size < t.size for v in e.outvars), e
    assert sum(e.primitive.name == "dot_general" for e in jaxpr.eqns) == 1
    assert not hasattr(fused_rope, "_rotate_half")


@pytest.mark.parametrize("lanes", ["whole", "latent"])  # rows end in them
def test_the_model_s_rotary_rotates_the_lanes_from_first_on(lanes):
    d, first, d2 = LANES[lanes]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 3, 40, d),
                          F32).astype(jnp.bfloat16)
    theta = 50000.0
    inv = theta ** (-np.arange(0, d2, 2, dtype=np.float64) / d2)
    ang = np.arange(40, dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1), F32)
    sin = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1), F32)
    _close(_rotary(x, theta, first), _split_rope(x, cos, sin, first),
           jnp.bfloat16, "model rotary")


# ---------------------------------------------------------------------------
# the chip compiler's word (compile only: a described v5e, no chip)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("shape,first,theta", [
    ((2, 16, 8192, 192), 128, 50000.0),     # moonlight-16b-a3b's q
    ((2, 32, 8192, 128), 0, 10000.0),       # trinity-mini's q
], ids=["moonlight-q", "trinity-q"])
def test_v5e_makes_the_rotary_one_pass_over_the_activation(
        one_chip, shape, first, theta, direction):
    """What kept ``model.mla_rope_ms`` at 37 ms (PR 35) was five passes
    with float32 and lane-sparse intermediates. The entry computation of
    the product form: no float32 array of the activation's size, no
    ``slice``, ``copy`` or ``pad`` of the activation, exactly one fusion
    that reads it, and that fusion writes the result."""
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def rotary(x):
        return _rotary(x, theta, first)

    fn = rotary if direction == "forward" else (
        lambda ct: jax.vjp(rotary, jnp.zeros(shape, jnp.bfloat16))[1](ct)[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(fn).lower(x).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):].splitlines()[1:]
    (param,) = [m.group(1) for line in entry for m in
                [re.match(r"\s*(%\S+) = \S+ parameter\(0\)", line)] if m]
    size = int(np.prod(shape))
    readers = []
    for line in entry:
        # "<result type> <opcode>(%operand, ...), attributes"
        result, _, operands = line.partition(" = ")[2].partition("(%")
        for dims in re.findall(r"f32\[([\d,]+)\]", result):
            assert np.prod([int(n) for n in dims.split(",")]) < size, line[:300]
        if param in re.findall(r"%[\w.\-]+", "%" + operands.split(")")[0]):
            readers.append(line)
    # one reader, a fusion: so no slice, copy, pad or transpose of it either
    assert len(readers) == 1, [line[:200] for line in readers]
    (line,) = readers
    assert " fusion(" in line and "kind=kOutput" in line, line[:300]
    assert line.lstrip().startswith("ROOT "), line[:300]
