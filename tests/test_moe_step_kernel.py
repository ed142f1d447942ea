"""``ops/moe_step.py``: the one-token expert step as one pass over the held
stacks that reads only the experts the call's live tokens picked, interpreted
on the CPU (Mosaic's own rules for the cell's shape:
``tests/test_tpu_compile.py``; values and time on the chip:
``chip_smoke.check_moe_at_cell_shape``). At ``tests/test_granite_hybrid.py``'s
size: 2 picks of a 12-wide router, 6 experts held, fused GLU, a shared expert.

- KERNEL: ``moe_mlp`` through the pass against dense dispatch (the ``ref``
  backend) over touched sets — none, one, some, all, one live row, picks on
  absent experts only — with the UNTOUCHED experts' weights and every other
  layer's slice of the stacks filled with NaN: dense dispatch turns NaN x 0
  into NaN, so a finite result equal to the reference computed on clean
  weights proves they were not read into it. The stacks as a ``StackedLeaf``
  at an index other than 0, at 0, and as a layer's own leaf; bfloat16.
- RULE: ``moe_mlp`` takes the pass only where the input shows that it applies.
- ENGINE: ``decode_step`` through the kernel equals the ``ref`` backend and
  counts the same experts touched; it read those and no others.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_smoke import _router_picking
from finchat_tpu.models import llama
from finchat_tpu.models.llama import StackedLeaf, init_params, moe_mlp
from finchat_tpu.models.quant import quantize_stacked
from finchat_tpu.ops import moe_step
from tests.test_granite_hybrid import CONFIG, _decode, _engine, _one_layer, _tokens

E, R = CONFIG.n_experts, CONFIG.moe_router_width  # 6 held of 12
# each row's two picks; rows are [B=2, S=5] in order, ``live`` masks them
CASES = {
    "none_live": dict(picks=[(0, 3)] * 10, live=[False] * 10),
    "one_expert": dict(picks=[(4, 7)] * 10),
    "some_experts": dict(picks=[(1, 7), (5, 1), (2, 9), (5, 2), (1, 11)] * 2),
    "every_expert": dict(picks=[(0, 1), (2, 3), (4, 5), (1, 4), (3, 9)] * 2),
    "one_live_row": dict(picks=[(0, 1), (2, 3), (4, 5), (1, 4), (3, 9)] * 2,
                         live=[False] * 3 + [True] + [False] * 6),
    "absent_picks_only": dict(picks=[(7, 9), (9, 11), (11, 7), (6, 8), (10, 6)] * 2),
    "a_live_row_on_absent_picks_beside_inert_rows_on_held_ones": dict(
        picks=[(7, 9)] + [(0, 2)] * 9, live=[True] + [False] * 9),
}
STACKS = {"stacked_at_1_of_3": (3, 1), "stacked_at_0_of_2": (2, 0), "stacked_at_the_last_of_3": (3, 2),
          "a_layers_own_leaf": (0, 0)}


def _case(picks, live=None, dtype=jnp.float32, layers=3, index=1):
    """``(h, clean layer params, the same with the stacks as the kernel may
    read them and NaN everywhere else, live [2, 5], the touched set)``."""
    lp, h = _one_layer()
    live = np.ones(10, bool) if live is None else np.asarray(live)
    lp = {**lp, "router": jnp.asarray(_router_picking(h.reshape(10, -1), picks, R))}
    lp = {name: leaf if name == "router" else leaf.astype(dtype) for name, leaf in lp.items()}
    touched = sorted({e for row, on in zip(picks, live) if on for e in row if e < E})
    poisoned = dict(lp)
    for name in ("moe_in", "moe_out"):
        layer = jnp.full_like(lp[name], jnp.nan).at[jnp.asarray(touched, jnp.int32)].set(
            lp[name][jnp.asarray(touched, jnp.int32)])
        if layers:
            stack = jnp.full((layers, *layer.shape), jnp.nan, dtype).at[index].set(layer)
            poisoned[name] = StackedLeaf(stack, jnp.asarray(index, jnp.int32))
        else:
            poisoned[name] = layer
    return h.astype(dtype), lp, poisoned, jnp.asarray(live.reshape(2, 5)), touched


def _through_the_pass(h, lp, live):
    return jax.jit(lambda h, lp: moe_mlp(h, lp, CONFIG, live=live, backend="pallas-interpret"))(h, lp)


# --- KERNEL --------------------------------------------------------------------

@pytest.mark.parametrize("case", CASES)
def test_the_pass_equals_dense_dispatch_and_reads_no_untouched_expert(case):
    h, clean, poisoned, live, touched = _case(**CASES[case])
    want, (want_touched, want_read) = moe_mlp(h, clean, CONFIG, live=live)
    got, (got_touched, got_read) = _through_the_pass(h, poisoned, live)
    assert np.isfinite(np.asarray(got)).all()  # an inert row's too: no NaN was read into it
    on = np.asarray(live)
    np.testing.assert_allclose(np.asarray(got)[on], np.asarray(want)[on], rtol=1e-5, atol=2e-6)
    assert int(got_touched) == int(want_touched) == len(touched)
    assert (int(got_read), int(want_read)) == (len(touched), E)  # the pass's n; dense reads all
    if not touched:  # nothing routed here: the shared expert alone, in every row
        zeroed = {**clean, "moe_out": jnp.zeros_like(clean["moe_out"])}
        np.testing.assert_allclose(np.asarray(got), np.asarray(moe_mlp(h, zeroed, CONFIG)),
                                   rtol=1e-5, atol=2e-6)


@pytest.mark.parametrize("stacks", STACKS)
def test_the_pass_indexes_the_whole_stack_and_reads_no_other_layer(stacks):
    layers, index = STACKS[stacks]
    h, clean, poisoned, live, touched = _case(CASES["some_experts"]["picks"], layers=layers,
                                              index=index)
    want = moe_mlp(h, clean, CONFIG)
    got, (n_touched, n_read) = _through_the_pass(h, poisoned, live)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=2e-6)
    assert int(n_touched) == int(n_read) == len(touched) == 3


def test_without_a_live_mask_every_token_counts_as_live():
    h, clean, poisoned, _live, _touched = _case(CASES["some_experts"]["picks"])
    got = jax.jit(lambda h, lp: moe_mlp(h, lp, CONFIG, backend="pallas-interpret"))(h, poisoned)
    np.testing.assert_allclose(np.asarray(got), np.asarray(moe_mlp(h, clean, CONFIG)),
                               rtol=1e-5, atol=2e-6)


def test_the_pass_in_bfloat16_rounds_where_dense_dispatch_rounds():
    h, clean, poisoned, live, touched = _case(CASES["every_expert"]["picks"], dtype=jnp.bfloat16)
    want, _ = moe_mlp(h, clean, CONFIG, live=live)
    got, (_n, n_read) = _through_the_pass(h, poisoned, live)
    assert got.dtype == jnp.bfloat16 and int(n_read) == len(touched) == E
    # the same products rounded at the same places; the float32 sums in another order
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


MIB = 1 << 20


@pytest.mark.parametrize("block_bytes,width,want", [(2 * MIB, 768, 256), (3 * MIB, 768, 384),
                                                    (2 * MIB, 32, 32), (MIB, 640, 128),
                                                    (MIB, 200, 200), (8 * MIB, 768, 768)])
def test_a_tile_is_a_multiple_of_128_that_divides_the_width_or_the_width(block_bytes, width, want,
                                                                        monkeypatch):
    """Where an expert is cut, at a model width of 4,096 in bfloat16 (128
    columns are 1 MiB): the narrowest dividing tile whose block reaches the
    floor, else the width."""
    monkeypatch.setattr(moe_step, "_WHOLE_BYTES", 0)
    monkeypatch.setattr(moe_step, "_BLOCK_BYTES", block_bytes)
    assert moe_step.width_tile(width, 4096, 2) == want


@pytest.mark.parametrize("model,width,dim,itemsize,want", [
    ("granite-4.0-h-small", 768, 4096, 2, 256), ("deepseek-v3.2-exp", 2048, 7168, 2, 256),
    ("trinity-mini", 1024, 2048, 2, 1024), ("trinity-mini in float32", 1024, 2048, 4, 256),
    ("15 MiB an expert at granite's model width", 640, 4096, 2, 640)])
def test_the_served_shapes_tiles(model, width, dim, itemsize, want):
    """The rule as it stands, on the three shapes that cells serve: Granite's
    (18 MiB an expert) and DeepSeek's (84 MiB) are cut into 256 columns as
    they always were (blocks of 2 and 3.5 MiB), Trinity-Mini's 12 MiB expert
    is one grid step, as is one of 640 columns at Granite's model width."""
    tile = moe_step.width_tile(width, dim, itemsize)
    assert tile == want and width % tile == 0
    if tile != width:
        assert tile % 128 == 0 and 3 * dim * width * itemsize > 16 * MIB
        assert dim * tile * itemsize >= 2 * MIB > dim * (tile - 128) * itemsize


def test_the_kernel_alone_over_several_tiles_and_rows_that_are_no_whole_sublane_tile(monkeypatch):
    """Three tiles of 128 columns a expert and 5 tokens (padded to 8): slots
    past the touched ones rest on the last touched block."""
    monkeypatch.setattr(moe_step, "_WHOLE_BYTES", 0)
    monkeypatch.setattr(moe_step, "_BLOCK_BYTES", 64 * 128 * 4)
    assert moe_step.width_tile(384, 64, 4) == 128
    L, D, F, T = 2, 64, 384, 5
    ks = jax.random.split(jax.random.key(5), 4)
    w_in = jax.random.normal(ks[0], (L, E, D, 2 * F)) * D ** -0.5
    w_out = jax.random.normal(ks[1], (L, E, F, D)) * F ** -0.5
    h = jax.random.normal(ks[2], (T, D))
    picked = jnp.asarray([False, True, False, True, True, False])
    gates = jax.random.uniform(ks[3], (T, E)) * picked[None]
    ids, n, cols = moe_step.plan(picked, gates)
    assert np.asarray(ids).tolist() == [1, 3, 4, 0, 2, 5] and int(n[0]) == 3
    step = jax.jit(moe_step.moe_experts_step.__wrapped__, static_argnames=("interpret",))
    got = step(h, cols, ids, n, w_in, w_out, jnp.asarray([1], jnp.int32), interpret=True)
    act = jax.nn.silu(jnp.einsum("td,edf->tef", h, w_in[1][..., :F])) \
        * jnp.einsum("td,edf->tef", h, w_in[1][..., F:])
    want = jnp.einsum("tef,efd->td", act * gates[..., None], w_out[1])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=2e-6)


@pytest.mark.parametrize("tile", [512, 1024], ids=["two_tiles_of_512", "the_whole_expert"])
def test_an_expert_of_1024_through_the_pass_equals_dense_dispatch_in_bfloat16(tile, monkeypatch):
    """Trinity-Mini's expert width at a small model width, as one grid step
    (what the rule gives) and cut into two tiles of 512 columns: the same
    products rounded at the same places, the float32 partial sums over the
    expert width grouped one way or the other."""
    wide = dataclasses.replace(CONFIG, hidden_dim=1024)
    if tile != 1024:
        monkeypatch.setattr(moe_step, "_WHOLE_BYTES", 0)
        monkeypatch.setattr(moe_step, "_BLOCK_BYTES", wide.dim * tile * 2)
    assert moe_step.width_tile(1024, wide.dim, 2) == tile
    lp, h = _one_layer(wide)
    picks = CASES["every_expert"]["picks"]
    lp = {**lp, "router": jnp.asarray(_router_picking(h.reshape(10, -1), picks, R))}
    lp = {name: leaf if name == "router" else leaf.astype(jnp.bfloat16) for name, leaf in lp.items()}
    h, live = h.astype(jnp.bfloat16), jnp.ones((2, 5), bool)
    want, _ = moe_mlp(h, lp, wide, live=live)
    # jitted anew: the tile is read while the call is traced
    step = jax.jit(moe_step.moe_experts_step.__wrapped__, static_argnames=("interpret",))
    monkeypatch.setattr(moe_step, "moe_experts_step", step)
    got, (n_touched, n_read) = jax.jit(
        lambda h, lp: moe_mlp(h, lp, wide, live=live, backend="pallas-interpret"))(h, lp)
    assert got.dtype == jnp.bfloat16 and int(n_touched) == int(n_read) == E
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


# --- RULE ----------------------------------------------------------------------

def _forms(config, lp, tokens, backend):
    text = str(jax.make_jaxpr(lambda h: moe_mlp(h, lp, config, backend=backend))(
        jnp.zeros((1, tokens, config.dim), jnp.float32)))
    return {"touched": "pallas_call" in text, "grouped": "ragged_dot" in text}


def _mixtral_shaped():
    config = llama.PRESETS["moe-tiny"]  # 4 experts, 2 a token, separate gate / up / down
    lp = jax.tree.map(lambda x: x[0], init_params(config, jax.random.key(0))["layers"])
    return config, lp


def _unfused_sparse():
    config = dataclasses.replace(llama.PRESETS["moe-tiny"], n_experts=12,
                                 moe_router_width=12)  # 12 > 4 x 2: it routes sparsely
    assert config.moe_sparse and not config.moe_fused_glu
    lp = jax.tree.map(lambda x: x[0], init_params(config, jax.random.key(0))["layers"])
    return config, lp


def _quantized(mode):
    lp, _h = _one_layer()
    return CONFIG, {**lp, "moe_in": quantize_stacked(lp["moe_in"], mode),
                    "moe_out": quantize_stacked(lp["moe_out"], mode)}


RULE_CASES = {
    # (config and a layer's leaves, tokens, backend) -> the form's marks in the traced call
    "a_sparse_models_small_call_on_a_kernel_backend": (lambda: (CONFIG, _one_layer()[0]), 10,
                                                       "pallas-interpret", "touched"),
    "at_the_rules_most_tokens": (lambda: (CONFIG, _one_layer()[0]), 16, "pallas", "touched"),
    "past_the_rules_tokens": (lambda: (CONFIG, _one_layer()[0]), 17, "pallas", "grouped"),
    "on_the_ref_backend": (lambda: (CONFIG, _one_layer()[0]), 10, "ref", "dense"),
    "mixtrals_shape": (_mixtral_shaped, 10, "pallas-interpret", "dense"),
    "separate_gate_up_down_leaves": (_unfused_sparse, 10, "pallas-interpret", "dense"),
    "an_int8_stack": (lambda: _quantized("int8"), 10, "pallas-interpret", "dense"),
    "an_int4_stack": (lambda: _quantized("int4"), 10, "pallas-interpret", "dense"),
}


@pytest.mark.parametrize("case", RULE_CASES)
def test_moe_mlp_takes_the_pass_only_where_the_input_shows_it_applies(case, monkeypatch):
    monkeypatch.setattr(llama, "MOE_DENSE_TOKENS_MAX", 16)
    build, tokens, backend, form = RULE_CASES[case]
    config, lp = build()
    assert llama._moe_form(config, tokens, lp, backend) == form
    marks = _forms(config, lp, tokens, backend)
    assert marks == {"touched": form == "touched", "grouped": form == "grouped"}


# --- ENGINE --------------------------------------------------------------------

def test_decode_step_through_the_kernel_equals_the_ref_backend_and_counts_alike():
    """Two rows prefilled, then four decode steps feeding the same tokens (a
    third slot rides inert): every step's logits and counts, the interpreted
    kernels against ``ref``. Both count the same experts touched; the pass
    read those, dense dispatch every held one in each of the ten layers."""
    prompts = {0: _tokens(17, seed=1), 3: _tokens(9, seed=2)}
    feed = [{0: a, 3: b} for a, b in zip(_tokens(4, seed=3), _tokens(4, seed=4))]
    logits, counts = {}, {}
    for backend in ("ref", "pallas-interpret"):
        engine = _engine(backend)
        for slot, prompt in prompts.items():
            engine.set_page_table_row(slot, [1 + 4 * slot + k for k in range(4)])
            engine.prefill(slot, prompt)
        logits[backend], counts[backend] = [], []
        for step in feed:
            logits[backend].append(_decode(engine, step)[[0, 3]])
            counts[backend].append([int(n) for n in engine.moe_experts])
    np.testing.assert_allclose(np.stack(logits["pallas-interpret"]), np.stack(logits["ref"]),
                               atol=2e-4)
    for (touched, read), (ref_touched, ref_read) in zip(counts["pallas-interpret"], counts["ref"]):
        assert 0 < touched == ref_touched == read <= 10 * 4  # two rows x two picks, ten layers
        assert ref_read == 10 * E
