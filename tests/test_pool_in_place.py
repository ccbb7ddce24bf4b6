"""The paged step programs write each layer's pages in place: inside a step
a pool stack (L, N, ...) is one flat pool of L*N pages, layer l's page p at
l*N + p, carried through the layer scan. After a mixed and then a decode
step, under every pool dtype and on both attention forms (grouped K/V
pages over three scanned layers; latent pages over a leading dense layer
and two scanned expert layers): each layer's slice changed at exactly the
pages the step writes and nowhere else, the pages a layer wrote hold that
layer's keys, and the logits are the full forward pass's. The pools start
as noise, so a stray write into any page of any layer shows."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models import transformer
from repro.serving import kv_pool

PAGE, CHUNK, N_PAGES = 8, 16, 10
WINDOW = kv_pool.chunk_window_pages(CHUNK, PAGE)               # 3 pages
# lane 0: a 13-token prompt, its decode token in the middle of page 1;
# lane 1: an 8-token prompt, its decode token the first of page 1 (it
# crosses a page boundary); lane 2: idle, its row all scratch.
PROMPTS = (13, 8, 0)
TABLE = np.array([[1, 2, 3], [4, 5, 6], [0, 0, 0]], np.int32)


@pytest.fixture(scope="module", params=["grouped", "latent"])
def model(request):
    if request.param == "grouped":
        cfg = reduced(get_arch("pangu_1b"), groups=3)
    else:
        cfg = reduced(get_arch("pangu_ultra_moe"), groups=2)
        # every expert chosen: no router near-tie turns a rounding into
        # another expert (as in test_mla_moe)
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, top_k=cfg.moe.num_experts))
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(7),
                                         (len(PROMPTS), CHUNK), 1,
                                         cfg.vocab - 8), np.int32)
    return cfg, params, toks, {}


def _noise_pools(cfg, kv_bits):
    pools = transformer.init_paged_pools(cfg, N_PAGES, PAGE, kv_bits)
    keys = iter(jax.random.split(jax.random.PRNGKey(3), 64))

    def noise(a):
        k = next(keys)
        if a.dtype in (jnp.int8, jnp.uint8):
            return jax.random.randint(k, a.shape, 0, 255).astype(a.dtype)
        if a.dtype == jnp.float32:                        # scales
            return jax.random.uniform(k, a.shape, minval=0.01, maxval=0.1)
        return jax.random.normal(k, a.shape).astype(a.dtype)
    return jax.tree.map(noise, pools)


def _steps(model, kv_bits):
    """The pools before the mixed step, after it and after the decode
    step, with each step's logits (lanes 0 and 1); once a pool dtype."""
    cfg, params, toks, runs = model
    if kv_bits not in runs:
        runs[kv_bits] = _run(cfg, params, toks, kv_bits)
    return runs[kv_bits]


def _run(cfg, params, toks, kv_bits):
    pools0 = _noise_pools(cfg, kv_bits)
    n_new = jnp.asarray(PROMPTS, jnp.int32)
    q_start = jnp.zeros(3, jnp.int32)
    rows = transformer.pack_rows(toks, q_start, n_new, 2 * CHUNK)
    table = jnp.asarray(TABLE)
    mixed, pools1 = transformer.prefill_chunk_paged(
        params, pools0, table, table[:, :WINDOW], *rows, q_start, n_new,
        cfg)
    pos = jnp.asarray(PROMPTS, jnp.int32)
    decode, pools2 = transformer.decode_step_paged(
        params, pools1, table, toks[np.arange(3), PROMPTS], pos, cfg)
    return (pools0, pools1, pools2), (mixed[:2], decode[:2])


def _changed_pages(before, after):
    """{stack: (L, N) bool}: the pages any leaf of the stack changed at."""
    out = {}
    for name in before:
        diff = None
        for leaf in before[name]:
            a = np.asarray(before[name][leaf]).astype(np.float32)
            b = np.asarray(after[name][leaf]).astype(np.float32)
            d = (a != b).reshape(a.shape[:2] + (-1,)).any(-1)
            diff = d if diff is None else diff | d
        out[name] = diff
    return out


def _expected(pages):
    want = np.zeros(N_PAGES, bool)
    want[np.asarray(pages).reshape(-1)] = True
    return want


@pytest.mark.parametrize("step", ["mixed", "decode"])
def test_each_layer_writes_only_its_own_pages(model, kv_bits, step):
    """Each layer's slice of each stack changed at exactly the pages the
    step writes (the mixed step: every lane's three window pages, the idle
    lane's scratch page; the decode step: each lane's page at its position,
    page 1 of both working lanes, and the idle lane's scratch), and no page
    of another layer or outside that set moved."""
    pools, _ = _steps(model, kv_bits)
    i = {"mixed": 0, "decode": 1}[step]
    if step == "mixed":
        want = _expected(TABLE[:, :WINDOW])
    else:
        pos = np.asarray(PROMPTS)
        want = _expected(TABLE[np.arange(3), pos // PAGE])
    for name, changed in _changed_pages(pools[i], pools[i + 1]).items():
        for layer in range(changed.shape[0]):
            np.testing.assert_array_equal(
                changed[layer], want, err_msg=f"stack {name} layer {layer}")


def test_a_layers_pages_hold_that_layers_keys(model, kv_bits):
    """The prompt pages each layer wrote in the mixed step are its own:
    for grouped K/V, the keys read back from layer l's pages are closest to
    layer l's keys in the dense prefill, within a page scale's rounding;
    for latent rows, each layer's pages differ from every other layer's."""
    cfg, params, toks, _ = model
    (_, pools, _), _ = _steps(model, kv_bits)
    if cfg.mla is not None:
        used = np.concatenate([TABLE[b, :-(-n // PAGE)]
                               for b, n in enumerate(PROMPTS) if n])
        layers = [np.asarray(pools[name]["k"][i, used])
                  for name in pools for i in range(pools[name]["k"].shape[0])]
        assert len(layers) == cfg.n_layers
        for i in range(len(layers)):
            for j in range(i):
                assert (layers[i] != layers[j]).any(), (i, j)
        return
    n = np.asarray(PROMPTS[:2])
    _, caches = transformer.prefill(
        params, {"tokens": jnp.asarray(toks[:2]),
                 "lengths": jnp.asarray(n, jnp.int32)}, cfg, max_len=CHUNK)
    live = np.arange(CHUNK)[None, :] < n[:, None]               # (2, C)
    tol = {16: 0.03, 8: 0.06, 4: 0.35}[kv_bits]
    for name in pools:
        dense = np.asarray(caches[name]["k"], np.float32)[:, :, :CHUNK]
        for layer in range(dense.shape[0]):
            one = jax.tree.map(lambda a: a[layer], pools[name])
            got, _ = kv_pool.gather_kv(one, jnp.asarray(TABLE[:2, :2]))
            got = np.asarray(got, np.float32)[live]
            errs = [np.abs(got - d[live]).max() / np.abs(d[live]).max()
                    for d in dense]
            assert int(np.argmin(errs)) == layer, (name, layer, errs)
            assert errs[layer] < tol, (name, layer, errs)


def test_logits_match_the_full_forward(model, kv_bits):
    """The mixed step's logits at each prompt's last token and the decode
    step's at the next are the full forward pass's: as closely as bf16
    pages hold the keys, within a page scale's rounding for int8 and int4
    pages."""
    cfg, params, toks, _ = model
    _, (mixed, decode) = _steps(model, kv_bits)
    tol = {16: 0.03, 8: 0.06, 4: 0.35}[kv_bits]
    for b in range(2):
        n = PROMPTS[b]
        seq = jnp.asarray(toks[b:b + 1, :n + 1])
        full, _ = transformer.forward_train(params, {"tokens": seq}, cfg,
                                            remat=False)
        want = full[0, n - 1:n + 1]
        got = jnp.stack([mixed[b], decode[b]])
        scale = float(jnp.abs(want).max())
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=tol * scale, err_msg=f"lane {b}")
