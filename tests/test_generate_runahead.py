"""``GeneratorModel.generate`` runs its decode steps ahead of the host: the
tokens equal those of a loop that reads each token back before the next
step, no token reaches the host between dispatches, ``max_new_tokens``
tokens take ``max_new_tokens - 1`` decode steps, and the KV cache is
donated to each program, which updates it in place to the same numbers
as the layers unrolled one by one."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

PROMPT = "what did the fund report about its quarterly dividend yield"


@pytest.fixture(scope="module")
def gen():
    from repro.serving.engine import GeneratorModel
    return GeneratorModel(seed=0)


def prefilled(gen, n):
    """The prompt's first token (on the device) and its filled cache, for
    ``n`` tokens in all."""
    ids = gen.tokenizer.encode(PROMPT, gen.max_prompt)
    toks = jnp.asarray([[0] * (gen.max_prompt - len(ids)) + ids], jnp.int32)
    caches = gen._init_cache(gen.cfg, 1, gen.max_prompt + n)
    logits, caches = gen._prefill(gen.params, {"tokens": toks}, caches)
    return logits.argmax(-1).astype(jnp.int32)[:, None], caches


def synchronised(gen, n):
    """The reference: each token read to the host and fed back from it."""
    tok, caches = prefilled(gen, n)
    out = [int(tok[0, 0])]
    for i in range(n - 1):
        logits, caches = gen._decode(gen.params,
                                     jnp.asarray([[out[-1]]], jnp.int32),
                                     caches, gen.max_prompt + i)
        out.append(int(logits.argmax(-1)[0]))
    return out


@pytest.mark.parametrize("n", [1, 2, 16])
def test_tokens_equal_a_synchronised_loop(gen, n):
    tokens = gen.generate(PROMPT, n)
    assert len(tokens) == n
    assert tokens == synchronised(gen, n)


class OnDevice:
    """A device array that fails the test where it is read to the host;
    what is computed from it (``argmax``, ``astype``, indexing) is another
    such array."""

    def __init__(self, a):
        self.a = a

    def __getattr__(self, name):
        got = getattr(self.a, name)
        if callable(got):
            return lambda *args, **kw: OnDevice(got(*args, **kw))
        return got

    def __getitem__(self, i):
        return OnDevice(self.a[i])

    def _to_host(self, *_):
        raise AssertionError("a token was read to the host")

    __array__ = __int__ = __index__ = __float__ = __bool__ = _to_host
    tolist = item = _to_host


def test_no_token_reaches_the_host_between_dispatches(gen):
    """Every logit and token of the loop is an :class:`OnDevice`: reading
    one to the host before the loop returns fails."""
    n, decode = 16, gen._decode

    def decoded(params, tok, caches, cache_len):
        logits, caches = decode(params, tok.a, caches, cache_len)
        return OnDevice(logits), caches
    tok, caches = prefilled(gen, n)
    gen._decode = decoded
    try:
        rest = gen.decode_ahead(OnDevice(tok), caches, n - 1)
    finally:
        gen._decode = decode
    assert len(rest) == n - 1
    assert all(t.shape == (1, 1) and t.dtype == jnp.int32 for t in rest)
    tokens = [int(tok[0, 0])] + [int(t.a[0, 0]) for t in rest]
    assert tokens == synchronised(gen, n)


@pytest.mark.parametrize("n", [1, 2, 16])
def test_a_wrapped_decode_sees_one_logit_per_token(gen, n):
    """Wrapped as a benchmark wraps ``_prefill`` and ``_decode``: the
    largest logit of each program call, kept on the device."""
    prefill, decode, top = gen._prefill, gen._decode, []

    def prefilled_(params, batch, caches):
        logits, caches = prefill(params, batch, caches)
        top.append([logits.max(axis=-1)])
        return logits, caches

    def decoded(params, tok, caches, cache_len):
        logits, caches = decode(params, tok, caches, cache_len)
        top[-1].append(logits.max(axis=-1))
        return logits, caches
    gen._prefill, gen._decode = prefilled_, decoded
    try:
        for _ in range(2):
            gen.generate(PROMPT, n)
    finally:
        gen._prefill, gen._decode = prefill, decode
    assert [len(t) for t in top] == [n, n]
    assert np.asarray(jnp.concatenate(top[0])).shape == (n,)


def test_the_cache_passed_in_is_donated(gen):
    caches = gen._init_cache(gen.cfg, 1, gen.max_prompt + 2)
    toks = jnp.zeros((1, gen.max_prompt), jnp.int32)
    logits, filled = gen._prefill(gen.params, {"tokens": toks}, caches)
    assert all(a.is_deleted() for a in jax.tree.leaves(caches))
    tok = logits.argmax(-1).astype(jnp.int32)[:, None]
    _, after = gen._decode(gen.params, tok, filled, gen.max_prompt)
    assert all(a.is_deleted() for a in jax.tree.leaves(filled))
    assert not any(a.is_deleted() for a in jax.tree.leaves(after))


@pytest.mark.parametrize("arch,window,slots", [
    pytest.param("stablelm-1.6b", 0, None, id="stablelm-1.6b"),
    pytest.param("gemma3-12b", 0, None, id="gemma3-12b"),
    pytest.param("zamba2-2.7b", 0, None, id="zamba2-2.7b"),
    pytest.param("rwkv6-1.6b", 0, None, id="rwkv6-1.6b"),
    # ring buffers of 16 slots that the 24-token prompt has wrapped
    pytest.param("gemma3-12b", 16, None, id="gemma3-12b-ring-wrapped"),
    # per-slot lengths, as ContinuousBatcher._decode_all passes them
    pytest.param("stablelm-1.6b", 0, (24, 13, 5), id="stablelm-1.6b-per-slot"),
])
def test_donated_caches_match_the_unrolled_layers(arch, window, slots):
    """Attention, ring-buffer, SSM and RWKV caches: a prefill and two
    decode steps whose programs are donated the caches, which the layer
    scan carries and updates in place, give the same logits and caches
    bit for bit as the layers unrolled one by one without donation."""
    import dataclasses
    from repro import configs
    from repro.models import forward, init_cache, init_params
    cfg = configs.get_config(arch).reduced()
    if window:
        cfg = dataclasses.replace(cfg, sliding_window=window)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = 1 if slots is None else len(slots)
    toks = jax.random.randint(jax.random.PRNGKey(1), (batch, 24), 0,
                              cfg.vocab_size)
    lens = 24 if slots is None else jnp.asarray(slots, jnp.int32)

    def run(scanned):
        def program(mode):
            def step(p, b, c, n):
                logits, c, _ = forward(p, cfg, b, mode=mode, caches=c,
                                       cache_len=n, remat=False,
                                       unroll_layers=not scanned)
                return logits[:, -1], c
            if mode == "prefill":
                return jax.jit(lambda p, b, c: step(p, b, c, 0),
                               donate_argnums=2 if scanned else ())
            return jax.jit(step, donate_argnums=2 if scanned else ())
        pre, dec = program("prefill"), program("decode")
        logits, caches = pre(params, {"tokens": toks},
                             init_cache(cfg, batch, 32))
        out = [logits]
        for n in (lens, lens + 1):
            tok = out[-1].argmax(-1).astype(jnp.int32)[:, None]
            logits, caches = dec(params, {"tokens": tok}, caches, n)
            out.append(logits)
        return [np.asarray(a) for a in out + jax.tree.leaves(caches)]
    for a, b in zip(run(True), run(False), strict=True):
        np.testing.assert_array_equal(a, b)
