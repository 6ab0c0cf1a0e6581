"""The packed Adam / SGD sweeps read and write the optimizer state where it
lives: between the step's flat 1-D state buffers and the
``apex_tpu_packed_*`` kernel stands nothing but the ``(n // 128, 128)``
view, whose ``(8, 128)`` tile is the 1-D buffer's own 1024-element HBM
tile (a bitcast on the chip; ``docs/packed_optimizers.md``).

What a CPU can count of that is the jaxpr: every state operand of the
kernel is an input of the step behind that one reshape, every state
result an output of the step behind the reshape back. That the view is
free is the chip compiler's word: the last tests compile the sweep for a
described v5e (no chip needed) and read the compiled text.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from apex_tpu.ops.packed_optimizer import LANES
from apex_tpu.optimizers import FusedAdam, FusedSGD

SHAPES = {"w": (40, 50), "b": (17,), "e": (3, 1024)}


def _tree(seed, dtype=jnp.bfloat16, scale=1.0):
    rng = np.random.RandomState(seed)
    return {k: jnp.asarray(rng.randn(*s) * scale, dtype)
            for k, s in SHAPES.items()}


# ---------------------------------------------------------------------------
# the jaxpr walk
# ---------------------------------------------------------------------------
def _find_kernel(jaxpr, path=()):
    """``(jaxpr, eqn, path)`` of the one ``apex_tpu_packed_*`` kernel;
    ``path`` lists the enclosing ``(jaxpr, eqn, sub-jaxpr position)``
    from the step down (the overflow-skip ``cond``, a ``jit``)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = str(eqn.params.get("name")
                       or eqn.params["name_and_src_info"])
            if "apex_tpu_packed_" in name:
                found.append((jaxpr, eqn, path))
            continue
        if eqn.primitive.name == "cond":
            # branch 0 is the step taken; cond's operand 0 is the index
            sub, shift = eqn.params["branches"][0].jaxpr, 1
        elif "jaxpr" in eqn.params:
            sub = eqn.params["jaxpr"]
            sub, shift = getattr(sub, "jaxpr", sub), 0
        else:
            continue
        found += _find_kernel(sub, path + ((jaxpr, eqn, shift),))
    return found


def _producer(jaxpr, var):
    for eqn in jaxpr.eqns:
        if var in eqn.outvars:
            return eqn
    return None


def _consumers(jaxpr, var):
    return [e for e in jaxpr.eqns if var in e.invars]


def _is_view(eqn, to_flat):
    """The one equation the design allows: ``(n,) <-> (n // 128, 128)``."""
    if eqn.primitive.name != "reshape" or eqn.params["dimensions"] is not None:
        return False
    src, dst = eqn.invars[0].aval.shape, eqn.outvars[0].aval.shape
    flat, view = (dst, src) if to_flat else (src, dst)
    return len(flat) == 1 and view == (flat[0] // LANES, LANES)


def _back_to_step_input(jaxpr, var, path):
    """Follow ``var`` up to an input of the step; returns that input and
    the equations crossed on the way (enclosing ``cond`` / ``jit``
    boundaries are not equations on the value)."""
    crossed = []
    while True:
        while var not in jaxpr.invars:
            eqn = _producer(jaxpr, var)
            assert eqn is not None, f"{var} has no producer"
            crossed.append(eqn)
            assert len(eqn.invars) == 1, \
                f"state operand computed by {eqn.primitive.name}"
            var = eqn.invars[0]
        if not path:
            return var, crossed
        (outer, call, shift), path = path[-1], path[:-1]
        var = call.invars[jaxpr.invars.index(var) + shift]
        jaxpr = outer
        assert not isinstance(var, jcore.Literal)


def _forward_to_step_output(jaxpr, var, path):
    """Follow ``var`` down to an output of the step; returns the
    equations crossed."""
    crossed = []
    while True:
        while var not in jaxpr.outvars:
            users = _consumers(jaxpr, var)
            assert len(users) == 1, \
                f"state result has {len(users)} consumers before the output"
            crossed.append(users[0])
            var = users[0].outvars[0]
        assert not _consumers(jaxpr, var), "equation after a state output"
        if not path:
            return crossed
        (outer, call, _), path = path[-1], path[:-1]
        var = call.outvars[jaxpr.outvars.index(var)]
        jaxpr = outer


def _adam(**kw):
    return FusedAdam(lr=1e-2, weight_decay=0.1, packed=True,
                     master_weights=True, packed_interpret=True, **kw)


def _case_adam_step():
    opt = _adam()
    params = _tree(0)
    state = opt.init(params)
    fn = lambda g, s, p, f: opt.step(g, s, p, found_inf=f, grad_scale=8.0)  # noqa: E731
    return fn, (_tree(1), state, params, jnp.asarray(False)), state, 3


def _case_adam_step_flat():
    opt = _adam()
    state = opt.init(_tree(0))
    g = state.spec.pack(_tree(1))
    fn = lambda g, s, f: opt.step_flat(g, s, found_inf=f, grad_scale=8.0)  # noqa: E731
    return fn, (g, state, jnp.asarray(False)), state, 3


def _case_sgd_step():
    opt = FusedSGD(lr=0.1, momentum=0.9, packed=True, master_weights=True,
                   packed_interpret=True)
    params = _tree(0)
    state = opt.init(params)
    fn = lambda g, s, p, f: opt.step(g, s, p, found_inf=f)  # noqa: E731
    return fn, (_tree(1), state, params, jnp.asarray(False)), state, 2


_CASES = {"adam.step": _case_adam_step,
          "adam.step_flat": _case_adam_step_flat,
          "sgd.step": _case_sgd_step}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_state_reaches_the_kernel_and_returns_as_a_view(case, monkeypatch):
    """Each fp32 state buffer the step is handed is the kernel's operand
    behind the ``(n // 128, 128)`` view and nothing else; each aliased
    result is the step's output behind the view back, with no equation
    after it. (The count a CPU can give of "no relayout".)"""
    fn, args, state, n_state = _CASES[case]()
    # trace the step the chip runs: off-TPU ``FusedAdam.step`` unpacks the
    # new bf16 params from the fp32 master result instead of ``p_out``
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    closed = jax.make_jaxpr(fn)(*args)
    monkeypatch.undo()
    top = closed.jaxpr
    (jaxpr, kernel, path), = _find_kernel(top)

    flat_in = jax.tree_util.tree_leaves(args)
    state_bufs = [b for b in (state.exp_avg, state.exp_avg_sq,
                              state.master_params)
                  if b is not None and b.ndim == 1 and b.shape[0] > 1]
    assert len(state_bufs) == n_state
    state_invars = {top.invars[i] for i, leaf in enumerate(flat_in)
                    if any(leaf is b for b in state_bufs)}
    assert len(state_invars) == n_state

    aliases = dict(kernel.params["input_output_aliases"])
    assert len(aliases) == n_state, aliases
    reached = set()
    for i_in, i_out in aliases.items():
        operand = kernel.invars[i_in]
        assert operand.aval.shape[1] == LANES and operand.aval.dtype == jnp.float32
        step_input, crossed = _back_to_step_input(jaxpr, operand, path)
        assert len(crossed) == 1 and _is_view(crossed[0], to_flat=False), \
            [str(e) for e in crossed]
        assert step_input in state_invars
        reached.add(step_input)

        result = kernel.outvars[i_out]
        crossed = _forward_to_step_output(jaxpr, result, path)
        assert len(crossed) == 1 and _is_view(crossed[0], to_flat=True), \
            [str(e) for e in crossed]
    assert reached == state_invars

    # the bf16 gradient enters behind the same view (no (rows, 1024) form)
    grad = kernel.invars[1]
    assert grad.aval.shape[1] == LANES
    assert _is_view(_producer(jaxpr, grad), to_flat=False)


# ---------------------------------------------------------------------------
# same float32 update, same bf16 recast, same skip-on-overflow
# ---------------------------------------------------------------------------
def test_three_packed_adam_steps_bit_exact_against_pytree():
    """The kernel bodies (interpreter) over the new view give, bit for
    bit, the pytree ``FusedAdam``'s parameters, masters and moments."""
    params = _tree(0)
    ref = FusedAdam(lr=1e-2, weight_decay=0.1, master_weights=True)
    pk = _adam()
    step = lambda opt: jax.jit(  # noqa: E731
        lambda g, s, p: opt.step(g, s, p, grad_scale=8.0))
    p_ref, s_ref = params, ref.init(params)
    p_pk, s_pk = params, pk.init(params)
    for seed in (1, 2, 3):
        g = _tree(seed, scale=8.0)
        p_ref, s_ref = step(ref)(g, s_ref, p_ref)
        p_pk, s_pk = step(pk)(g, s_pk, p_pk)
    spec = s_pk.spec
    for got, want in (
            (p_pk, p_ref),
            (spec.unpack(s_pk.master_params, cast=False), s_ref.master_params),
            (spec.unpack(s_pk.exp_avg, cast=False), s_ref.exp_avg),
            (spec.unpack(s_pk.exp_avg_sq, cast=False), s_ref.exp_avg_sq)):
        for k in SHAPES:
            assert got[k].dtype == want[k].dtype
            np.testing.assert_array_equal(
                np.asarray(got[k], np.float32), np.asarray(want[k], np.float32))
    assert int(s_pk.step) == int(s_ref.step) == 3
    # padding stays zero under the sweep (norms over the buffer stay exact)
    pad = ~spec.valid_mask()
    for buf in (s_pk.exp_avg, s_pk.exp_avg_sq, s_pk.master_params):
        assert not np.asarray(buf)[pad].any()


@pytest.mark.parametrize("how", ["step", "step_flat"])
def test_overflow_skipped_step_changes_nothing(how):
    """``found_inf`` true: params, masters, both moments and the step
    count are the ones handed in — through the ``cond`` of ``step`` and
    through the kernel's own ``noop`` of ``step_flat``."""
    opt = _adam()
    params = _tree(0)
    state = opt.init(params)
    g = _tree(1)
    # one real step first, so that the moments are not all zero
    params, state = jax.jit(lambda g, s, p: opt.step(g, s, p))(g, state, params)
    before = [np.asarray(x).copy() for x in (
        state.exp_avg, state.exp_avg_sq, state.master_params)]
    bad = jax.tree_util.tree_map(lambda x: x.at[(0,) * x.ndim].set(jnp.inf), g)
    inf = jnp.asarray(True)
    if how == "step":
        new_params, new_state = jax.jit(
            lambda g, s, p: opt.step(g, s, p, found_inf=inf))(
                bad, state, params)
        for k in SHAPES:
            np.testing.assert_array_equal(
                np.asarray(new_params[k], np.float32),
                np.asarray(params[k], np.float32))
    else:
        new_state = jax.jit(
            lambda g, s: opt.step_flat(g, s, found_inf=inf))(
                state.spec.pack(bad), state)
    assert int(new_state.step) == int(state.step) == 1
    for was, now in zip(before, (new_state.exp_avg, new_state.exp_avg_sq,
                                 new_state.master_params)):
        assert now.shape == was.shape and now.ndim == 1
        np.testing.assert_array_equal(np.asarray(now), was)


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


def _entry(compiled) -> str:
    text = compiled.as_text()
    return text[text.index("ENTRY"):]


def _compile_uncached(jitted, *shapes):
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it out of the cache."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return jitted.lower(*shapes).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("kernel", ["adam", "sgd"])
def test_v5e_compile_moves_no_state(one_chip, kernel):
    """Donated 1-D buffers in, 1-D buffers out, compiled for v5e: the
    entry computation holds the kernel and bitcasts — no ``copy``, no
    ``reshape``, no fusion — and the program needs no temporary."""
    from apex_tpu.ops.packed_optimizer import (
        packed_adam_apply, packed_sgd_apply)

    n = 128 * 65536  # 8.4 M elements, 128 chunks
    S = lambda dt: jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)  # noqa: E731
    if kernel == "adam":
        fn = lambda g, m, v, p: packed_adam_apply(  # noqa: E731
            g, m, v, p, param_dtype=jnp.bfloat16, lr=1e-4, bc1=0.1,
            bc2=0.001, inv_scale=0.5, noop=False, use_kernel=True)
        shapes = (S(jnp.bfloat16),) + (S(jnp.float32),) * 3
        donate = (1, 2, 3)
    else:
        fn = lambda g, b, p: packed_sgd_apply(  # noqa: E731
            g, b, p, param_dtype=jnp.bfloat16, lr=0.1, first_run=False,
            momentum=0.9, use_kernel=True)
        shapes = (S(jnp.bfloat16),) + (S(jnp.float32),) * 2
        donate = (1, 2)
    compiled = _compile_uncached(jax.jit(fn, donate_argnums=donate), *shapes)
    entry = _entry(compiled)
    assert f"apex_tpu_packed_{kernel}" in entry and "tpu_custom_call" in entry
    for moved in (" copy(", " reshape(", " transpose(", " fusion("):
        assert moved not in entry, entry
    assert entry.count(" bitcast(") >= 2 * len(shapes) - 1
    assert compiled.memory_analysis().temp_size_in_bytes == 0
