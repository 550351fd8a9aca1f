"""Per-kernel correctness: interpret-mode Pallas vs the pure-jnp oracle in
ref.py, swept over shapes and dtypes (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import intra, network
from repro.kernels import ref
from repro.kernels.bisect_alloc import bisect_alloc
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mlstm_chunk import mlstm_chunk

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5), jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _tol(dtype):
    return TOL[jnp.bfloat16] if dtype == jnp.bfloat16 else TOL[jnp.float32]


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (2, 4, 2, 256, 64),
    (1, 8, 1, 512, 128),   # MQA
    (2, 2, 2, 128, 256),   # MHA, gemma head_dim
    (1, 4, 4, 384, 64),    # non-pow2 seq (3 blocks of 128)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(b, hq, hkv, s, d, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                          interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("window", [32, 128, 1024])
def test_flash_attention_sliding_window(window):
    b, hq, hkv, s, d = 1, 4, 1, 512, 64
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d))
    k = jax.random.normal(ks[1], (b, hkv, s, d))
    v = jax.random.normal(ks[2], (b, hkv, s, d))
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=128, block_k=128, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=2e-5, atol=2e-5)


def test_flash_attention_non_causal():
    b, hq, hkv, s, d = 2, 2, 2, 256, 64
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d))
    k = jax.random.normal(ks[1], (b, hkv, s, d))
    v = jax.random.normal(ks[2], (b, hkv, s, d))
    out = flash_attention(q, k, v, causal=False, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# decode_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,s,d,valid", [
    (2, 8, 2, 512, 64, 512),
    (2, 8, 2, 512, 64, 317),   # partial cache
    (1, 4, 1, 2048, 128, 1500),
    (4, 4, 4, 256, 256, 100),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(b, hq, hkv, s, d, valid, dtype):
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (b, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    out = decode_attention(q, k, v, jnp.int32(valid), block_k=256, interpret=True)
    expect = ref.decode_attention_ref(q, k, v, jnp.int32(valid))
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), **_tol(dtype)
    )


# ---------------------------------------------------------------------------
# bisect_alloc (the paper's kernel)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,k", [(5, 18), (16, 25), (64, 40), (3, 130)])
def test_bisect_alloc_matches_core_solver(n, k):
    svc, _ = network.sample_services(jax.random.key(4), n, k_max=k)
    b = jax.random.uniform(jax.random.key(5), (n,), minval=0.2, maxval=4.0)
    t_star, b_alloc = bisect_alloc(svc.alpha, svc.t_comp, b, interpret=True)
    t_ref, b_ref = ref.bisect_alloc_ref(svc.alpha, svc.t_comp, b)
    np.testing.assert_allclose(np.asarray(t_star), np.asarray(t_ref), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(b_alloc), np.asarray(b_ref), rtol=1e-3, atol=1e-5)


def test_bisect_alloc_budget_and_equalization():
    svc, _ = network.sample_services(jax.random.key(6), 12, k_max=30)
    b = jnp.full((12,), 1.5)
    t_star, b_alloc = bisect_alloc(svc.alpha, svc.t_comp, b, interpret=True)
    np.testing.assert_allclose(np.asarray(b_alloc.sum(-1)), 1.5, rtol=1e-5)
    finish = svc.t_comp + svc.alpha / jnp.maximum(b_alloc, 1e-30)
    finish = jnp.where(svc.mask, finish, t_star[:, None])
    np.testing.assert_allclose(
        np.asarray(finish), np.asarray(t_star)[:, None] * np.ones_like(finish),
        rtol=1e-3,
    )


@pytest.mark.parametrize("batch", [None, 64])
@pytest.mark.parametrize("kernel", ["bisect_alloc", "mbdf_demand",
                                    "dual_demand", "market_clear"])
@pytest.mark.parametrize("n,k", [(10, 48), (8, 128), (3, 130), (129, 7)])
def test_padded_shape_is_what_each_wrapper_launches(kernel, n, k, batch):
    """``tiling.padded_shape`` is the (rows, lanes) every wrapper hands
    ``pallas_call``, in grid steps of ``tiling.row_tile`` rows;
    ``policy.launch_shape`` reports it for the fleet engine's work counts.
    Under vmap the three bisection kernels launch once on the folded rows
    of the whole batch; the megakernel launches each set on its own."""
    from jaxpr_shapes import pallas_launches

    from repro.kernels import dual_demand, market_clear
    from repro.kernels.tiling import padded_shape

    lead = () if batch is None else (batch,)
    a = jnp.ones(lead + (n, k), jnp.float32)
    calls = {
        "bisect_alloc": lambda a: bisect_alloc(a, a, jnp.ones(a.shape[:1]),
                                               interpret=True),
        "mbdf_demand": lambda a: market_clear.mbdf_demand(
            a, a, jnp.ones((a.shape[0], 5)), 0.5, interpret=True),
        "dual_demand": lambda a: dual_demand.dual_demand(a, a, 1.0,
                                                         interpret=True),
        "market_clear": lambda a: market_clear.market_clear(
            a, a, 10.0, 0.0, interpret=True),
    }
    fn = calls[kernel] if batch is None else jax.vmap(calls[kernel])
    rows = (batch or 1) * n
    if kernel == "market_clear":
        per_set, lanes = padded_shape(n, k, market_clear.TILE_N)
        want = ((batch or 1) * per_set, lanes, per_set)
    else:
        want = _launch(kernel, rows, k)
    assert pallas_launches(fn, a) == [want]


def _row_blocks(kernel):
    """The launch rule of a folded kernel, as its wrapper takes it."""
    from repro.kernels import dual_demand, market_clear
    from repro.kernels import bisect_alloc as bisect_mod

    return {"bisect_alloc": bisect_mod.row_blocks,
            "dual_demand": dual_demand.row_blocks,
            "mbdf_demand": market_clear.mbdf_row_blocks}[kernel]


def _launch(kernel, rows, k):
    """(rows, lanes, row block) the kernel's wrapper launches on."""
    return tuple(_row_blocks(kernel)(rows, k)[:3])


def test_row_tile_pads_under_a_sublane_group_per_grid_step():
    """The fewest grid steps of at most ``ROW_CAP`` rows whose block fits
    the VMEM budget, split evenly: every step pads under 8 rows, and a
    launch over whole blocks pads none.  Up to 128 lanes the cap binds
    (320 rows a step at 640 rows, the sweep cells' launch), for each
    kernel's VMEM planes; wider, the block fits the budget, and a launch
    takes more than one step where one block of all its rows would not."""
    from repro.kernels.tiling import (ROW_CAP, SUBLANES, VMEM_BUDGET,
                                      padded_shape)

    for blocks in map(_row_blocks, FOLDED):
        for k in (1, 48, 128):
            for rows in list(range(1, 2 * ROW_CAP + 20)) + [640, 4096, 8256]:
                padded, lanes, tile, _ = blocks(rows, k)
                steps = padded // tile
                assert tile % 8 == 0 and tile <= ROW_CAP
                assert steps == -(-rows // ROW_CAP)
                assert 0 <= padded - rows < 8 * steps
                assert lanes == -(-k // 128) * 128
        assert blocks(640, 48).tile == 320
        assert blocks(10, 48)[:3] == (16, 128, 16)
        for k in (384, 1000, 1792, 4096, 8192):
            for rows in (8, 160, 640, 1000, 2560):
                padded, _, tile, step_bytes = blocks(rows, k)
                row_bytes = step_bytes // tile   # a block's bytes a row
                steps = padded // tile
                assert tile % SUBLANES == 0 and tile <= ROW_CAP
                assert step_bytes <= VMEM_BUDGET
                assert 0 <= padded - rows < 8 * steps
                one_block = -(-rows // SUBLANES) * SUBLANES
                if one_block * row_bytes > VMEM_BUDGET:
                    assert steps > 1
                # One step fewer would pass the cap or the budget.
                if steps > 1:
                    per_step = -(-rows // (steps - 1))
                    fewer = -(-per_step // SUBLANES) * SUBLANES
                    assert (fewer > ROW_CAP
                            or fewer * row_bytes > VMEM_BUDGET)
        # Past the budget at even one plane, so at every kernel's planes.
        with pytest.raises(ValueError, match="VMEM budget"):
            blocks(8, VMEM_BUDGET // (4 * SUBLANES) + 128)
    assert padded_shape(10, 48, 16) == (16, 128)


def _services(rng, lead, n, k):
    """Ragged service sets with a fully-inactive row, masked slots slower
    than every valid client, and the third operand of each kernel: a
    budget per row (some 0), an ascending 5-bid price grid around each
    row's p_max (some above it), and a dual price per row."""
    shape = lead + (n, k)
    count = rng.integers(1, k + 1, size=lead + (n, 1))
    count[..., 0, :] = 0
    mask = np.arange(k) < count
    alpha = np.where(mask, rng.uniform(0.01, 0.3, shape), 0.0)
    t_comp = np.where(mask, rng.uniform(0.01, 0.06, shape), 99.0)
    p_max = 1.0 / np.maximum(alpha.sum(-1, keepdims=True), 1e-3)
    prices = p_max * np.sort(rng.uniform(0.05, 1.2, lead + (n, 5)), -1)
    budget = np.where(rng.uniform(size=lead + (n,)) < 0.2, 0.0,
                      rng.uniform(0.2, 4.0, lead + (n,)))
    lam = rng.uniform(0.05, 0.5, lead + (n,))
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    return f32(alpha), f32(t_comp), {"bisect_alloc": f32(budget),
                                     "mbdf_demand": f32(prices),
                                     "dual_demand": f32(lam)}


def _folded_kernel(kernel):
    from repro.kernels import dual_demand, market_clear

    return {
        "bisect_alloc": lambda a, t, x: bisect_alloc(a, t, x, interpret=True),
        "mbdf_demand": lambda a, t, x: market_clear.mbdf_demand(
            a, t, x, 0.5, interpret=True),
        "dual_demand": lambda a, t, x: dual_demand.dual_demand(
            a, t, x, interpret=True),
    }[kernel]


def _assert_bitwise(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _stacked_loop(fn, *args, in_axes):
    """The unbatched calls, one per batch element, stacked."""
    size = next(x.shape[0] for x, ax in zip(args, in_axes) if ax == 0)
    outs = [fn(*(x[i] if ax == 0 else x for x, ax in zip(args, in_axes)))
            for i in range(size)]
    return jax.tree.map(lambda *ys: jnp.stack(ys), *outs)


FOLDED = ["bisect_alloc", "mbdf_demand", "dual_demand"]


@pytest.mark.parametrize("kernel", FOLDED)
@pytest.mark.parametrize("e,n,k", [(64, 10, 48), (3, 129, 7)])
def test_vmapped_wrapper_is_bitwise_a_loop_of_unbatched_calls(kernel, e, n,
                                                              k):
    """The fold changes which rows share a launch, never a row's result:
    each row's arithmetic is its own.  The vmapped call is one launch over
    the folded padding of all e * n rows."""
    from jaxpr_shapes import pallas_launches

    fn = _folded_kernel(kernel)
    alpha, t_comp, third = _services(np.random.default_rng(e * n + k), (e,),
                                     n, k)
    args = (alpha, t_comp, third[kernel])
    _assert_bitwise(jax.vmap(fn)(*args),
                    _stacked_loop(fn, *args, in_axes=(0, 0, 0)))
    assert pallas_launches(jax.vmap(fn), *args) == [_launch(kernel, e * n, k)]


@pytest.mark.parametrize("kernel", FOLDED)
def test_vmapped_wrapper_broadcasts_an_unbatched_operand(kernel):
    """A budget, price grid or dual price shared by every batch element
    (``in_axes=None``) is broadcast into the fold; a scalar dual price
    too, shared or one per batch element."""
    fn = _folded_kernel(kernel)
    alpha, t_comp, third = _services(np.random.default_rng(5), (4,), 10, 48)
    shared = third[kernel][0]
    _assert_bitwise(jax.vmap(fn, in_axes=(0, 0, None))(alpha, t_comp, shared),
                    _stacked_loop(fn, alpha, t_comp, shared,
                                  in_axes=(0, 0, None)))
    if kernel == "dual_demand":
        lam = jnp.float32(0.2)
        _assert_bitwise(jax.vmap(fn, in_axes=(0, 0, None))(alpha, t_comp, lam),
                        _stacked_loop(fn, alpha, t_comp, lam,
                                      in_axes=(0, 0, None)))
        lams = jnp.float32([0.1, 0.2, 0.3, 0.4])
        _assert_bitwise(jax.vmap(fn)(alpha, t_comp, lams),
                        _stacked_loop(fn, alpha, t_comp, lams,
                                      in_axes=(0, 0, 0)))


@pytest.mark.parametrize("kernel", FOLDED)
def test_nested_vmap_folds_level_by_level_into_one_launch(kernel):
    """vmap of vmap: the inner batch folds into the rows, then the outer
    one, so the call is still one launch over every row."""
    from jaxpr_shapes import pallas_launches

    fn = _folded_kernel(kernel)
    alpha, t_comp, third = _services(np.random.default_rng(6), (2, 3), 10, 48)
    args = (alpha, t_comp, third[kernel])
    nested = jax.vmap(jax.vmap(fn))
    want = _stacked_loop(jax.vmap(fn), *args, in_axes=(0, 0, 0))
    _assert_bitwise(nested(*args), want)
    assert pallas_launches(nested, *args) == [_launch(kernel, 60, 48)]


@pytest.mark.parametrize("kernel", FOLDED)
def test_a_launch_split_by_the_vmem_budget_is_bitwise_one_block(kernel,
                                                                monkeypatch):
    """At 1792 lanes the budget splits 500 rows (under ``ROW_CAP``) into
    two grid steps; each row's outputs are bitwise what one block of all
    the rows gives them (a budget large enough for one step,
    interpreted)."""
    from jaxpr_shapes import pallas_launches

    from repro.kernels import tiling

    fn = _folded_kernel(kernel)
    alpha, t_comp, third = _services(np.random.default_rng(7), (), 500, 1792)
    args = (alpha, t_comp, third[kernel])
    (rows, _, tile), = pallas_launches(fn, *args)
    assert rows // tile == 2
    split = fn(*args)
    monkeypatch.setattr(tiling, "VMEM_BUDGET", 1 << 30)
    jax.clear_caches()
    (rows, _, tile), = pallas_launches(fn, *args)
    assert rows == tile
    _assert_bitwise(split, fn(*args))
    monkeypatch.undo()
    jax.clear_caches()


# ---------------------------------------------------------------------------
# mlstm_chunk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,s,dh,chunk", [
    (2, 2, 256, 64, 128),
    (1, 4, 512, 128, 128),
    (2, 1, 256, 64, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mlstm_chunk_matches_parallel_oracle(b, h, s, dh, chunk, dtype):
    ks = jax.random.split(jax.random.key(7), 5)
    q = jax.random.normal(ks[0], (b, h, s, dh), dtype)
    k = jax.random.normal(ks[1], (b, h, s, dh), dtype) / jnp.sqrt(dh).astype(dtype)
    v = jax.random.normal(ks[2], (b, h, s, dh), dtype)
    ig = (jax.random.normal(ks[3], (b, h, s)) * 0.5).astype(dtype)
    fg = (jax.random.normal(ks[4], (b, h, s)) * 0.5 + 2.0).astype(dtype)
    out = mlstm_chunk(q, k, v, ig, fg, chunk=chunk, interpret=True)
    expect = ref.mlstm_chunk_ref(q, k, v, ig, fg)
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else dict(rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), **tol
    )
