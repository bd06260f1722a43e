"""EVA attention (``evabyte``) over a paged cache whose rows are not
positions: the row map's properties, ``HybridTransformer`` through the
pool against the benchmark's plain reference (which has no cache and no
row map), the same through the engine with run-ahead on, and the
reference's controls. CPU, ``preset://evabyte-tiny``: windows of 32,
chunks of 4, pages of 8 or 16, so that windows, chunks and pages all cross
inside a short test."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import kernel_cost_eva
from benchmark.architectures import evabyte as reference
from llmq_tpu.engine.engine import EngineConfig, EngineCore
from llmq_tpu.engine.sampling import SamplingParams
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.models import cache, hybrid
from llmq_tpu.models.presets import _EVABYTE, get_preset
from llmq_tpu.models.transformer import build_model, make_kv_pages
from llmq_tpu.ops.attention import eva_context, eva_row, eva_table_pages
from llmq_tpu.parallel import make_mesh

EVA = get_preset("evabyte-tiny")
W, C = EVA.eva_window, EVA.eva_chunk
# The configuration as the benchmark's file would state it.
HF = dict(
    _EVABYTE, vocab_size=304, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=4, intermediate_size=128,
    window_size=W, chunk_size=C, num_pred_heads=1,
)
# Float32 against float32, one summation order apart; read 7e-6 at most.
TOL = 2e-4


def seeded_params():
    """The preset's tree with no leaf at a value a reference could drop:
    norm weights and the two learned vectors are drawn, not 0 and tiny."""
    params = hybrid.init_params(EVA, jax.random.key(1), dtype=jnp.float32)
    keys = iter(jax.random.split(jax.random.key(5), 8))
    stack = dict(params["stack0"])
    for name in ("ln1", "ln2"):
        stack[name] = 0.1 * jax.random.normal(next(keys), stack[name].shape)
    for name in ("eva_mu", "eva_phi"):
        stack[name] = 1.0 + 2.0 * jax.random.normal(next(keys), stack[name].shape)
    final = 0.1 * jax.random.normal(next(keys), params["final_norm"].shape)
    return dict(params, stack0=stack, final_norm=final)


PARAMS = seeded_params()
MODEL = build_model(EVA)
PREFILL = jax.jit(MODEL.prefill)
DECODE = jax.jit(MODEL.decode)


# --- (a) the row map ----------------------------------------------------------


SHAPES = [(32, 4, 8), (32, 4, 16), (64, 8, 8), (48, 4, 16), (2048, 16, 128)]


@pytest.mark.parametrize("window, chunk, page", SHAPES)
def test_the_rows_a_query_attends_are_one_run_from_zero(window, chunk, page):
    """A cache played through position by position: a token lands on a
    row nothing still reads, a window's close finds its exact rows where
    it looks and leaves its summaries straight before the next window's
    first row, and what a query attends is rows ``[0, eva_row(pos)]``:
    every earlier window's summaries in order, then its own window."""
    S = window // chunk
    cache = {}  # row -> what it holds; a row that is absent is free or dead
    for pos in range(3 * window + 5):
        w, o = divmod(pos, window)
        row = eva_row(pos, window, chunk)
        assert row not in cache, "a row still to be read is overwritten"
        cache[row] = ("token", pos)
        attended = [cache[r] for r in range(eva_context(pos + 1, window, chunk))]
        assert attended == (
            [("summary", v, j) for v in range(w) for j in range(S)]
            + [("token", w * window + i) for i in range(o + 1)]
        )
        if o == window - 1:  # the step compacts the window it has closed
            first = S * w
            exact = [cache.pop(first + i) for i in range(window)]
            assert exact == [("token", w * window + i) for i in range(window)]
            for j in range(S):
                cache[first + j] = ("summary", w, j)
            assert eva_row(pos + 1, window, chunk) == first + S
    assert eva_context(0, window, chunk) == 0 and eva_row(-1, window, chunk) == -1


@pytest.mark.parametrize("window, chunk, page", SHAPES)
def test_table_pages_is_the_brute_force_maximum_and_only_grows(window, chunk, page):
    top = 3 * window + 7 if window < 1000 else 2 * window + 300
    rows = [eva_row(p, window, chunk) for p in range(top)]
    starts = (0, 1, window - 1, window, window + 3, 2 * window)
    step = 1 if window < 1000 else 37
    for start in starts:
        before = 0
        for stop in range(start + 1, top, step):
            pages = eva_table_pages(start, stop, window, chunk, page)
            assert pages == max(rows[start:stop]) // page + 1, (start, stop)
            assert pages >= before
            before = pages
    assert eva_table_pages(5, 0, window, chunk, page) == 0
    # a start past the last position is taken as the last position
    assert eva_table_pages(top, 9, window, chunk, page) == rows[8] // page + 1


def test_the_row_map_of_arrays_is_the_row_map_of_ints():
    pos = np.arange(-1, 3 * W + 2)
    want = [eva_row(int(p), W, C) for p in pos]
    assert list(np.asarray(eva_row(jnp.asarray(pos), W, C))) == want
    assert list(np.asarray(eva_context(jnp.asarray(pos) + 1, W, C))) == [r + 1 for r in want]
    # the published sizes: 16 pages of a finished window become one
    assert eva_table_pages(0, 12288, 2048, 16, 128) == 21
    assert eva_context(6144, 2048, 16) == 2 * 128 + 2048
    assert eva_context(6145, 2048, 16) == 3 * 128 + 1


# --- (b) the model through the pool against the plain reference ---------------


def through_the_pool(prompts, n_decode, page, places=20, bucket=128):
    """Logits of a batch of sequences (token lists, each at least its
    prompt + ``n_decode`` long): ONE prefill of all the prompts, then a
    decode step a token for all of them, fed the sequences' own tokens."""
    B = len(prompts)
    lengths = np.asarray([n for _, n in prompts], np.int32)
    tokens = np.zeros((B, bucket), np.int32)
    for b, (ids, n) in enumerate(prompts):
        tokens[b, :n] = ids[:n]
    bt = 1 + np.arange(B * places, dtype=np.int32).reshape(B, places)
    k, v = make_kv_pages(EVA, B * places + 1, page, jnp.float32)
    logits, k, v = PREFILL(PARAMS, tokens, lengths, k, v, bt)
    rows = [[np.asarray(logits[b])] for b in range(B)]
    for j in range(n_decode):
        fed = np.asarray([ids[n + j] for ids, n in prompts], np.int32)
        logits, k, v = DECODE(PARAMS, fed, lengths + j, k, v, bt, np.ones((B,), bool))
        for b in range(B):
            rows[b].append(np.asarray(logits[b]))
    return [np.stack(r) for r in rows]


def reference_logits(ids, first, count, control=None):
    return np.asarray(
        reference.forward_logits(
            PARAMS, HF, list(ids[: first + count]), list(range(first, first + count)),
            control=control,
        )
    )


@pytest.mark.parametrize(
    "page, lengths",
    [(8, (30,)), (8, (32,)), (16, (33, 64)), (16, (70, 31))],
    ids=["before_a_boundary", "on_a_boundary", "after_and_on_two_rows", "third_window_two_rows"],
)
def test_prefill_then_decode_through_the_pool_is_the_plain_reference(page, lengths):
    """Prompts that end before, on and after a window boundary, then 70
    decode steps, which close two windows: logits of every step against
    the reference's one full forward pass (no cache, no row map)."""
    n_decode = 70
    rng = np.random.default_rng(sum(lengths))
    seqs = [(list(rng.integers(1, 300, size=n + n_decode + 1)), n) for n in lengths]
    got = through_the_pool(seqs, n_decode, page)
    for (ids, n), logits in zip(seqs, got):
        want = reference_logits(ids, n - 1, n_decode + 1)
        assert np.abs(logits - want).max() < TOL, (n, np.abs(logits - want).max())


def test_a_prompt_of_several_windows_prefills_as_the_reference():
    """100 tokens in a 128 bucket: three complete windows leave their
    summaries alone, the fourth's 4 exact rows follow them."""
    rng = np.random.default_rng(3)
    ids = list(rng.integers(1, 300, size=110))
    (got,) = through_the_pool([(ids, 100)], 6, page=8)
    assert np.abs(got - reference_logits(ids, 99, 7)).max() < TOL


# --- (d) every control moves the reference ------------------------------------


@pytest.mark.parametrize("control", reference.CONTROLS)
def test_every_control_moves_the_references_logits(control):
    rng = np.random.default_rng(9)
    ids = list(rng.integers(1, 300, size=80))
    sound = reference_logits(ids, 60, 20)
    moved = np.abs(reference_logits(ids, 60, 20, control=control) - sound).max()
    assert moved > 50 * TOL, (control, moved)


# --- (c) the engine: pages by the row map, run-ahead, recompute preemption -----


def make_core(**engine) -> EngineCore:
    options = dict(
        max_num_seqs=4, max_model_len=160, page_size=8, num_pages=60,
        kv_dtype=jnp.float32, min_prefill_bucket=16,
    )
    options.update(engine)
    return EngineCore(
        EVA, PARAMS, ByteTokenizer(), mesh=make_mesh(tensor_parallel=1),
        engine_config=EngineConfig(**options),
    )


def greedy(n):
    return SamplingParams(temperature=0.0, max_tokens=n, ignore_eos=True)


REQUESTS = [("r0", "ab" * 15, 70), ("r1", "cde" * 15, 40), ("r2", "fg" * 32, 50), ("r3", "h" * 20, 20)]


def serve(core, requests, each_step=None):
    for rid, prompt, n in requests:
        core.add_request(rid, prompt=prompt, params=greedy(n))
    outs = {}
    for _ in range(3000):
        for out in core.step():
            outs[out.rid] = out
        if each_step is not None:
            each_step(core)
        if not core.has_work:
            return outs
    raise AssertionError("engine stalled")


def assert_served_the_references_best(outs, requests):
    """Every served token is the reference's best at its position, to the
    float32 tolerance on the logits (a tie within it is either's)."""
    for rid, prompt, n in requests:
        ids = ByteTokenizer().encode(prompt)
        served = outs[rid].token_ids
        assert len(served) == n
        logits = reference_logits(ids + served, len(ids) - 1, n)
        regret = logits.max(axis=1) - logits[np.arange(n), served]
        assert regret.max() < TOL, (rid, regret.max())


def test_the_engine_serves_the_reference_and_counts_pages_by_the_row_map():
    """Four sequences of unequal lengths, run-ahead on: at every turn of
    the host a running sequence holds the places its next row needs and no
    more than the row map can ask for; a sequence of 100 tokens peaks at 6
    places where a row a position would take 13."""
    core = make_core()
    core.spans.set(True)
    page = core.cfg.page_size
    peak = {}

    def pages_follow_the_row_map(core):
        # the span's fields are the benchmark's own count of attended rows
        seqs = core._decodable_seqs()
        rows = core.cache.decode_span(
            [s.num_tokens for s in seqs], core._decode_kernel_plan
        )
        assert rows["summary_rows"] + rows["window_rows"] == sum(
            kernel_cost_eva.attended_rows(s.num_tokens, window=W, chunk=C) for s in seqs
        )
        for seq in core.scheduler.running.values():
            n = seq.num_tokens
            assert len(seq.pages) >= eva_table_pages(n, n + 1, W, C, page)
            # never more than every row up to the run-ahead's reach needs
            assert len(seq.pages) <= eva_table_pages(0, n + 4 * page, W, C, page)
            peak[seq.rid] = max(peak.get(seq.rid, 0), len(seq.pages))

    outs = serve(core, REQUESTS, pages_follow_the_row_map)
    assert_served_the_references_best(outs, REQUESTS)
    stats = core.stats()
    # r0 closes windows at 31, 63, 95; r1 at 63; r2 at 95; r3 at 31
    assert stats["eva_windows_closed"] == 6 and stats["preemptions"] == 0
    assert peak["r0"] <= eva_table_pages(0, 100 + 4 * page, W, C, page) < -(-100 // page)
    dispatches = [s for s in core.spans.dump()["spans"] if s["name"] == "decode_dispatch"]
    assert dispatches
    for s in dispatches:
        assert s["summary_rows"] % (W // C) == 0 and 1 <= s["window_rows"] <= s["rows"] * W
        assert s["live_pages"] <= -(-(s["summary_rows"] + s["window_rows"]) // page) + s["rows"]
    assert any(s["summary_rows"] for s in dispatches)


def test_admission_takes_the_pages_of_the_compressed_prompt():
    """A prompt of 70 tokens (two complete windows, 6 exact rows) is
    admitted with 3 places of 8 rows, not the 9 its positions would take."""
    core = make_core()
    core.add_request("x", prompt="k" * 70, params=greedy(4))
    core.step()
    (seq,) = core.scheduler.running.values()
    assert len(seq.pages) <= eva_table_pages(0, 70 + 4 * 8, W, C, 8) < 9
    assert core.scheduler._pages_needed(70) == (2 * 8 + 6) // 8 + 1 == 3


def test_recompute_preemption_serves_the_same_logits():
    """A pool too small for all three: a victim is preempted and
    prefilled again over prompt + output, windows it had closed in decode
    now summarised by the prefill: the same rows, so the same tokens."""
    requests = [(f"p{i}", f"pr {i} " * 6, 44) for i in range(3)]
    core = make_core(num_pages=17, page_size=4, max_model_len=96)
    outs = serve(core, requests)
    assert core.stats()["preemptions"] >= 1
    assert_served_the_references_best(outs, requests)


REFUSED = {
    "prefix_caching": (dict(enable_prefix_caching=True), "enable_prefix_caching"),
    "spec_tokens": (dict(spec_tokens=2), "spec_tokens=2"),
    "preempt_swap": (dict(preempt_mode="swap"), "preempt_mode=swap"),
    "prefill_chunk_size": (dict(prefill_chunk_size=16), "prefill_chunk_size"),
}


@pytest.mark.parametrize("option, named", REFUSED.values(), ids=REFUSED.keys())
def test_what_an_eva_pattern_cannot_do_is_refused_by_name(option, named):
    with pytest.raises(ValueError) as refusal:
        make_core(**option)
    text = str(refusal.value)
    assert named in text and "rows are not positions" in text


def test_the_model_refuses_a_cut_cache_by_name():
    with pytest.raises(NotImplementedError, match="rows are not positions"):
        MODEL.prefill_chunk()
    k, v = make_kv_pages(EVA, 4, 8, jnp.float32)
    with pytest.raises(NotImplementedError, match="rows are not positions"):
        MODEL.decode(
            PARAMS, np.zeros((1,), np.int32), np.zeros((1,), np.int32), k, v,
            np.ones((1, 2), np.int32), np.ones((1,), bool), sliding_window=4,
        )


def test_the_published_pool_takes_the_latent_kernel():
    """The plan is read off the pool's shape: rows of 8,192 bf16 values
    (a token's V then K at 32 heads of 128), ``rank`` 4,096, pages of 128
    rows are whole tiles, so one TPU reads them with the latent pool's
    kernel (``stats()["decode_kernel"]``: ``latent_live``); the CPU and
    the tiny preset's pages of 8 float32 rows take the XLA loop."""
    from llmq_tpu.ops import dispatch

    big = get_preset("evabyte-6.5b-pp4")
    assert (cache.paged_rank(big), cache.latent_pool_width(big)) == (4096, 8192)
    assert dispatch.latent_decode_kernel_plan(
        4096, 128, 8192, jnp.bfloat16, None, "pallas"
    ) == "latent_live"
    assert dispatch.latent_decode_kernel_plan(4096, 128, 8192, jnp.bfloat16, None, "xla") == "xla"
    assert make_core().stats()["decode_kernel"] == "xla"
