"""A configuration's reference module, found by name.

The default module (`chipbench/references/decoder_moe.py`) is the code
that was `reference.py`, `weights.py` and the model half of `counts.py`:
its weights, reference outputs and counts are pinned to checksums and
readings taken from that code before it moved.  A configuration that names
a module of its own is checked against it with nothing added but files
under a root, and the program's configuration is held to every key of the
file's `model` block.
"""
import contextlib
import json
import os
import shutil

import jax
import numpy as np
import pytest

from chipbench import bench, counts, manifest, readers, system, traffic

HERE = os.path.dirname(__file__)
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
DEFAULT = manifest.load_reference(manifest.DEFAULT_REFERENCE)

# a tiny Qwen3-shaped model block, in the served bfloat16
TINY_QWEN3 = {"num_layers": 2, "d_model": 64, "num_heads": 4,
              "num_kv_heads": 2, "head_dim": 16, "num_experts": 8, "top_k": 2,
              "expert_d_ff": 32, "vocab_size": 256, "rope_theta": 1000000.0,
              "norm_eps": 1e-06, "qk_norm": True, "router_renorm": True,
              "dtype": "bfloat16"}
PROMPT = ((np.arange(13) * 37 + 5) % 256).astype(np.int32)
LEAVES = ["['embed']", "['final_norm']", "['lm_head']",
          "['stages'][0]['attn']['k_norm']", "['stages'][0]['attn']['q_norm']",
          "['stages'][0]['attn']['wk']", "['stages'][0]['attn']['wo']",
          "['stages'][0]['attn']['wq']", "['stages'][0]['attn']['wv']",
          "['stages'][0]['ffn']['experts']['w_down']",
          "['stages'][0]['ffn']['experts']['w_gate']",
          "['stages'][0]['ffn']['experts']['w_up']",
          "['stages'][0]['ffn']['router']", "['stages'][0]['ln_attn']",
          "['stages'][0]['ln_ffn']"]
# checksum() of the weights (every leaf in LEAVES order), of the reference
# hidden states of PROMPT and of the head's logits at its last position,
# from `weights.make`, `reference.hidden_states` and `reference.head_logits`
# as they stood before the move; `control` is the float8 control's hidden
# states
PARENT = {
    7: {"weights": [-47.860081057566276, 19364.05294111848,
                    -23.199708150954393],
        "hidden": [-34.37930470908759, 813.1389200728516, -40.60623483202653],
        "logits": [11.462837100028992, 293.80385967755564, 6.86389297246933],
        "control": [-36.956990827806294, 812.813989314042,
                    -38.41127575538121]},
    2**33 + 5: {"weights": [-179.5444148588358, 19365.239111688392,
                            -70.3987819424583],
                "hidden": [-45.13535244530067, 905.9630392307356,
                           -39.636359019670635],
                "logits": [6.573230005800724, 300.5313029223853,
                           14.414491064846516]},
}


def checksum(arrays):
    """Sum, sum of squares and a signed sum (which any reordering moves) of
    the arrays' values, in float64."""
    x = np.concatenate([np.asarray(a, np.float64).ravel() for a in arrays])
    sign = np.random.default_rng(0).choice([-1.0, 1.0], x.size)
    return np.array([x.sum(), (x * x).sum(), (sign * x).sum()])


def assert_checksum(got, want):
    # rounding of the host's CPU may move the last bits; a changed draw,
    # scale, norm or layer moves these by far more
    want = np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-4 * np.sqrt(want[1]))


@pytest.mark.parametrize("seed", sorted(PARENT))
def test_default_module_matches_the_parent(seed):
    w = DEFAULT.weights(TINY_QWEN3, seed)
    leaves = jax.tree_util.tree_flatten_with_path(w)[0]
    assert [jax.tree_util.keystr(p) for p, _ in leaves] == LEAVES
    assert_checksum(checksum([a for _, a in leaves]), PARENT[seed]["weights"])
    h = DEFAULT.hidden_states(TINY_QWEN3, w, PROMPT)
    assert h.shape == (len(PROMPT), TINY_QWEN3["d_model"])
    assert_checksum(checksum([h]), PARENT[seed]["hidden"])
    assert_checksum(checksum([DEFAULT.head_logits(TINY_QWEN3, w, h[-1])]),
                    PARENT[seed]["logits"])


def test_default_control_matches_the_parent():
    w = DEFAULT.weights(TINY_QWEN3, 7)
    h = DEFAULT.hidden_states(TINY_QWEN3, w, PROMPT, control=True)
    assert_checksum(checksum([h]), PARENT[7]["control"])


def test_readers_match_the_parent_on_a_synthetic_run():
    with open(os.path.join(ROOT, "chipbench/configs/qwen3-235b-a22b.json")) \
            as f:
        m = json.load(f)["model"]
    reqs = [bench.Request(rid=i, length=n, due=0.1 * i, done=0.1 * i + t)
            for i, (n, t) in enumerate([(128, 0.31), (777, 0.52),
                                        (2048, 1.27), (1000, 9.0)])]
    reqs[3].done = None  # never answered: counts for neither
    run = bench.Run(model=m, peak=counts.peaks("TPU v5 lite"), setup_s=1.0,
                    requests=reqs, window_s=5.0,
                    counters={"moe_launches": 40.0}, experts_per_launch=32,
                    reference=DEFAULT, weights=None,
                    tokens={r.rid: np.zeros(r.length, np.int32)
                            for r in reqs})
    # n * top_k * layers over the finished prompts, as before the move
    assert readers.real_pairs(run) == 23624 == (128 + 777 + 2048) * 8
    assert readers.mfu_ttft(run) == 0.3380833607967126


def test_a_configuration_without_a_reference_key_gets_the_default():
    man = manifest.load_manifest(ROOT)
    for w in man["workloads"]:
        assert manifest.load_cell(w["name"], ROOT).reference is DEFAULT


@pytest.mark.parametrize("name", ["no_such_model", "a/b", ""])
def test_a_missing_or_badly_named_module_is_refused(name):
    with pytest.raises(manifest.ManifestError):
        manifest.load_reference(name)


# appended to a copy of the default module: the reference's hidden states
# moved by 5% of each row's norm
PLANT = '''

_exact_hidden_states = hidden_states


def hidden_states(m, w, tokens, control=False):
    h = _exact_hidden_states(m, w, tokens, control)
    return h + 0.05 * np.linalg.norm(h, axis=-1, keepdims=True) \\
        / np.sqrt(h.shape[-1])
'''


def _fixture_root(root, module_source):
    """A checkout that holds nothing of the benchmark but one cell's files:
    the fixture configuration, its mix and its reference module."""
    for d in ("configs", "references", "traffic"):
        (root / "chipbench" / d).mkdir(parents=True)
    shutil.copy(os.path.join(HERE, "fixtures", "tiny-own.json"),
                root / "chipbench/configs/tiny-own.json")
    shutil.copy(os.path.join(HERE, "fixtures", "tiny-mix.json"),
                root / "chipbench/traffic/tiny-mix.json")
    (root / "chipbench/references/tiny_own.py").write_text(module_source)
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny-own", "source": "test", "reduced": [],
                     "file": "chipbench/configs/tiny-own.json",
                     "why": "test"}],
        "workloads": [{"name": "tiny-own.mix", "config": "tiny-own",
                       "traffic": "tiny-mix", "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower",
                        "bound": 0.25, "source": "host_clock"}],
        "per_layer": []}))
    return manifest.load_cell("tiny-own.mix", str(root))


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Two prompts served on the CPU through `system.Server`, with the
    weights of the fixture cell's own module; the cell found once with a
    copy of the default module and once with a planted one."""
    with open(DEFAULT.__file__) as f:
        source = f.read()
    cells = {kind: _fixture_root(tmp_path_factory.mktemp(kind), text)
             for kind, text in (("copy", source),
                                ("planted", source + PLANT))}
    cell = cells["copy"]
    m = cell.config["model"]
    w = cell.reference.weights(m, 2**31 + 11)
    server = system.Server(cell.config, w, [12, 20], log=lambda _: None)
    arrivals = [traffic.Arrival(rid=0, due=0.0, length=12),
                traffic.Arrival(rid=1, due=0.05, length=20)]
    tokens = traffic.prompt_tokens(arrivals, m["vocab_size"], 2**31 + 11)
    reqs = bench.drive(server, arrivals, tokens, 0.2,
                       lambda _: contextlib.nullcontext())
    server.close()
    return cells, w, reqs, tokens


@pytest.mark.parametrize("kind", ["copy", "planted"])
def test_own_reference_module_decides_correct(served, kind):
    cells, w, reqs, tokens = served
    cell = cells[kind]
    assert cell.reference is not DEFAULT
    assert cell.reference.__file__.startswith(cell.root)
    v = bench.check(cell.reference, cell.config["model"], w, reqs, tokens,
                    cell.config["correct"])
    assert v["compared"] == 2
    if kind == "copy":
        assert v["correct"] is True, v["checks"]
    else:
        assert v["correct"] is False
        assert v["checks"]["hidden_err"]["value"] > 0.04


def _fixture_config():
    with open(os.path.join(HERE, "fixtures", "tiny-own.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("change,key", [
    ({"clip_qkv": 8.0}, "clip_qkv"),  # the program's ModelConfig lacks it
    ({"rope_theta": 10000.0}, "rope_theta"),  # the program runs 1e6
    ({"tie_embeddings": True}, "tie_embeddings"),  # a stated default
], ids=["lacking", "other-value", "other-default"])
def test_program_config_refuses_what_the_program_does_not_run(change, key):
    config = _fixture_config()
    config["model"].update(change)
    with pytest.raises(ValueError, match=key):
        system.program_config(config)


def test_program_config_takes_what_the_file_states():
    config = _fixture_config()
    config["model"]["num_shared_experts"] = 0  # states a default
    cfg = system.program_config(config)
    assert (cfg.num_layers, cfg.rope_theta, cfg.num_shared_experts) == \
        (2, 1000000.0, 0)


@pytest.mark.parametrize("change", [
    {"logit_softcap": 30.0}, {"num_shared_experts": 1},
    {"window_size": 4096}, {"clip_qkv": 8.0}, {"norm": "layernorm"},
], ids=["softcap", "shared-expert", "window", "clip_qkv", "norm"])
def test_default_module_refuses_what_it_does_not_compute(change):
    """A departure the default module does not model is refused by each of
    its functions, before it computes anything, naming the key."""
    m = dict(TINY_QWEN3, **change)
    key = next(iter(change))
    w = DEFAULT.weights(TINY_QWEN3, 0)
    for call in (lambda: DEFAULT.weights(m, 0),
                 lambda: DEFAULT.hidden_states(m, w, PROMPT),
                 lambda: DEFAULT.head_logits(m, w, np.zeros(64, np.float32)),
                 lambda: DEFAULT.prompt_flops(m, 13),
                 lambda: DEFAULT.routed_rows(m, w, PROMPT)):
        with pytest.raises(ValueError, match=key):
            call()


def test_default_module_takes_plain_values_stated():
    m = dict(TINY_QWEN3, num_shared_experts=0, logit_softcap=None,
             act="silu")
    assert DEFAULT.prompt_flops(m, 13) == DEFAULT.prompt_flops(TINY_QWEN3, 13)
