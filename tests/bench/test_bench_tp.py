"""The tensor-parallel adapter (``bench/models/dense_decoder_tp.py``) on four
host devices, at a reduced size of ``deepseek-67b-tp4``'s shape: grouped-
query attention with 4 query heads a KV head and 2 KV heads a device, and
an FFN whose 200 columns a device are no multiple of the block GEMM's column
block, so each device's GEMM pads.  Pallas kernels run in interpret mode
under the mesh, as they run compiled on the chip.

One subprocess (the host device count is fixed when JAX starts) makes the
weights on the mesh, serves through ``Engine`` and the harness, and prints
what the tests below assert."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

SCRIPT = textwrap.dedent("""
    import os, sys, json, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    ROOT, TINY = sys.argv[1], sys.argv[2]
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src"),
                    os.path.join(ROOT, "tests", "bench")]
    from pathlib import Path
    import numpy as np, jax
    import _tiny
    from bench import run as R
    from bench.registry import Registry
    from repro.core.cgra import select_block_shapes
    from repro.launch.sharding import tree_pspecs
    from repro.models import model as M
    from repro.serving import Engine

    SHAPE = dict(hidden_size=256, num_attention_heads=32,
                 num_key_value_heads=8, intermediate_size=800,
                 vocab_size=512)
    reg = Registry(ROOT)
    conf = reg.config("deepseek-67b-tp4")
    conf.update(SHAPE, num_hidden_layers=2, dtype="float32")
    engine = dict(max_batch=4, max_len=128, page_size=16, chunk_tokens=16,
                  n_pages=33, decode_chunk=4)
    ad = reg.module("models", conf["model"])
    ref = reg.module("reference", conf["model"])
    cfg = ad.arch(conf, "interpret")
    out = {"bn": select_block_shapes(16, 256, 200)[2]}

    made = ad.make_weights(cfg, jax.random.PRNGKey(5))
    want = jax.tree.leaves(ad.shardings(cfg))
    out["leaves"] = {
        jax.tree_util.keystr(p): [str(a.sharding.spec), a.sharding == ns,
                                  list(a.shape),
                                  [list(s.data.shape)
                                   for s in a.addressable_shards]]
        for (p, a), ns in zip(jax.tree_util.tree_flatten_with_path(made)[0],
                              want)}

    whole = reg.module("models", "dense_decoder").make_weights(
        cfg, jax.random.PRNGKey(5))
    out["same_values"] = all(
        bool((np.asarray(a) == np.asarray(b)).all())
        for a, b in zip(jax.tree.leaves(made), jax.tree.leaves(whole)))
    del whole

    eng = Engine(cfg, made, ad.engine_config(conf, engine, "interpret"))
    r = eng.runner
    out["params_kept"] = all(a is b for a, b in zip(jax.tree.leaves(made),
                                                    jax.tree.leaves(r.params)))
    out["pools"] = [[str(c.sharding.spec), c.sharding == ns, list(c.shape),
                     [list(s.data.shape) for s in c.addressable_shards]]
                    for c, ns in zip(jax.tree.leaves(r.caches),
                                     jax.tree.leaves(tree_pspecs(
                                         r.cache_specs, r.mesh)))]

    # chunked prefill then paged decode through the model's own steps on
    # the engine's sharded params and page pools
    chunk = jax.jit(r._traced(lambda p, c, t, pg, past, n: M.chunk_step(
        r.cfg, p, c, t, pg, past, n)))
    dec = jax.jit(r._traced(lambda p, c, t, pos, pg: M.decode_step(
        r.cfg, p, c, t, pos, pages=pg)))
    C, npp = engine["chunk_tokens"], engine["max_len"] // engine["page_size"]
    pages = np.arange(1, npp + 1, dtype=np.int32)[None]
    prompt = np.random.default_rng(0).integers(1, 512, 40).tolist()
    caches, got, rows = r.caches, [], []
    for past in range(0, len(prompt), C):
        n = min(C, len(prompt) - past)
        buf = np.zeros((1, C), np.int32)
        buf[0, :n] = prompt[past:past + n]
        lg, caches = chunk(r.params, caches, buf, pages, np.int32(past),
                           np.int32(n))
        got.append(np.asarray(lg)[0, -1, :512])
        rows.append(past + n - 1)
    toks = list(prompt)
    for _ in range(6):
        toks.append(int(np.argmax(got[-1])))
        lg, caches = dec(r.params, caches, np.array([[toks[-1]]], np.int32),
                         np.array([len(toks) - 1], np.int32), pages)
        got.append(np.asarray(lg)[0, -1, :512])
        rows.append(len(toks) - 1)
    want_l = ref.logits(conf, ad.reference_view(cfg, made), toks,
                        np.array(rows), pad_to=engine["max_len"])
    out["rows"] = rows
    out["logit_gap"] = float(np.abs(np.stack(got) - want_l).max())
    out["logit_scale"] = float(np.abs(want_l).max())
    del caches

    # the all-reduce counter over whole engine ticks
    rng = np.random.default_rng(1)
    eng.generate([rng.integers(1, 512, n).tolist() for n in (20, 37, 9)],
                 max_new=7)
    s = eng.stats
    out["ticks"] = [s.mixed_steps, s.chunks]
    out["collective_bytes"] = s.collective_bytes
    out["tp"] = r.tp
    del eng, r, made

    # the harness: a whole run of the tiny cell on the mesh
    root = Path(TINY)
    name = _tiny.make(root, "deepseek-67b-tp4", "chat-tp4", chips=4,
                      like="deepseek-67b-tp4.chat", **SHAPE)
    mix = root / "bench/traffic/tinychat-tp4.json"
    mix.write_text(json.dumps(dict(json.loads(mix.read_text()),
                                   rate_per_s=4.0)))
    res = R.run_cell(Registry(root), name, 2**33 + 5, 1.0, False,
                     jax.devices()[:4], kernel_mode="interpret",
                     t_start=time.time())
    out["run"] = {k: res[k] for k in ("correct", "attempted", "failed",
                                      "check", "metrics")}
    out["run"]["devices"] = res["device"]["count"]
    print("RESULT " + json.dumps(out))
""")

# the reduced shape: L layers of D, H query heads over K KV heads
L, D, H, K, F, V = 2, 256, 32, 8, 800, 512
C, B, DC = 16, 4, 4   # chunk rows, decode batch, decode chunk


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(ROOT),
                        str(tmp_path_factory.mktemp("tiny"))],
                       env=env, capture_output=True, text=True, timeout=600)
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    assert p.returncode == 0 and lines, p.stdout[-3000:] + p.stderr[-3000:]
    return json.loads(lines[-1][len("RESULT "):])


def test_each_device_pads_its_ffn_share(served):
    assert (F // 4) % served["bn"] != 0


def test_weights_are_made_where_the_program_places_them(served):
    """Every leaf comes out with the program's own sharding; the heads,
    KV heads, FFN columns and vocabulary rows are split four ways, so no
    device holds the whole model, and the engine moves nothing."""
    leaves = served["leaves"]
    assert all(same for _, same, _, _ in leaves.values())
    split = {"['embed']": 0, "['lm_head']": 1}
    for name in ("wq", "wk", "wv"):
        split[f"['stages'][0]['0']['mixer']['{name}']"] = 2
    split["['stages'][0]['0']['mixer']['wo']"] = 1
    split["['stages'][0]['0']['ffn']['w_gate']"] = 2
    split["['stages'][0]['0']['ffn']['w_up']"] = 2
    split["['stages'][0]['0']['ffn']['w_down']"] = 1
    for name, axis in split.items():
        _, _, shape, shards = leaves[name]
        assert len(shards) == 4
        for s in shards:
            assert s[axis] * 4 == shape[axis]
            assert s[:axis] + s[axis + 1:] == shape[:axis] + shape[axis + 1:]
    held = sum(_size(leaves[n][3][0]) for n in leaves)
    whole = sum(_size(v[2]) for v in leaves.values())
    assert held < whole / 3   # only the norm scales are on every device
    assert served["params_kept"]
    assert served["same_values"]  # as the one-device adapter draws them


def test_page_pools_are_made_on_their_kv_head_shards(served):
    """Each layer's k and v pool ``[L, pages, K, rows, d]`` holds 2 of the
    8 KV heads on each device, as the program places it."""
    pools = served["pools"]
    assert len(pools) == 2
    for spec, same, shape, shards in pools:
        assert same and shape == [L, 33, K, 16, D // H]
        assert shards == [[L, 33, K // 4, 16, D // H]] * 4


def _size(shape):
    n = 1
    for d in shape:
        n *= d
    return n


def test_prefill_and_decode_logits_agree_with_the_reference(served):
    """Three prompt chunks (16, 16 and 8 rows) and six decode steps,
    against the float32 reference over the same sharded weights.  Both
    sides are float32; they differ by summation order (a partitioned
    contraction, the kernels' blocked dots and online softmax), about 1e-6
    of logits of about 4, so 1e-4 leaves that thirty times of room and a
    bf16 rounding anywhere (about 1e-2) fails it."""
    assert served["rows"] == [15, 31, 39, 40, 41, 42, 43, 44, 45]
    assert served["logit_gap"] <= 1e-4, served
    assert served["logit_scale"] > 1.0


def test_collective_bytes_count_two_all_reduces_a_layer(served):
    """Each call all-reduces, per device, 2 x layers x rows x d_model f32
    values: a mixed step runs the chunk's C rows and one decode step of the
    B slots, a decode chunk DC decode steps of the B slots."""
    mixed, chunks = served["ticks"]
    assert mixed >= 3 and chunks >= 1
    per_row = 2 * L * D * 4
    assert served["collective_bytes"] == per_row * (
        mixed * (C + B) + chunks * DC * B)
    assert served["tp"] == 4


def test_the_harness_serves_the_cell_on_four_devices(served):
    """A whole run of the cell's configuration at the reduced shape, in
    float32: every served token is the reference's first choice (the tiny
    configuration's limit is 1e-8)."""
    run = served["run"]
    assert run["devices"] == 4
    assert run["correct"] is True, run["check"]
    assert run["attempted"] > 0 and run["failed"] == 0
    assert {"ttft_p90_s", "itl_p95_ms", "setup_s"} <= set(run["metrics"])
