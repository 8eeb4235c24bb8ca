"""Model modules: each configuration's weights, reference, operation counts
and state-insert warm-up come from the module its file names
(``bench/models/<model>.py``, ``decoder`` by default).

The default module gives what the benchmark read before model modules
existed: the weight trees, the reference's logits and the counts are pinned
at values recorded from the harness's own code before the move (checksums
of the bytes, on the CPU).  A configuration that names a module of its own
runs through ``harness.measure`` with nothing else under ``bench/`` edited,
and the check compares against that module's reference."""
import functools
import hashlib
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec, weights
from conftest import smoke_cell
from test_bench_faults import LIMITS

CELLS = {"qwen3-1.7b": "qwen3-1.7b.chat",
         "mixtral-8x7b": "mixtral-8x7b.gen-batch"}
DECODER = spec.model_module({})
TOY = pathlib.Path(__file__).parent / "data" / "toy_decoder.py"


def digest(tree) -> str:
    """sha256 over every leaf's path, dtype, shape and bytes."""
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}|{a.dtype}|{a.shape}|"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _published(name):
    bench = spec.benchmark()
    return spec.load_json(spec.config_file(bench, name))


TREES = {
    ("qwen3-1.7b", 5):
        "d9a5317a6dbcae914132460047de12bab352038c7cb09aed68d8746c18c2dcb6",
    ("qwen3-1.7b", 2**33 + 7):
        "52765a77919577d133341b0cc64479c51c7e36c53823b46fbe7384e4d6f9e290",
    ("mixtral-8x7b", 5):
        "f978cb68f3bd2c8f0d79e8a911da34f9438ce37c1a5b5bf9bba0f0406d43b993",
    ("mixtral-8x7b", 2**33 + 7):
        "4c8ef100a59deb40cf71821acc9267365f14f325c6a954d4e9c6f3cde8c48e3e",
}


@pytest.mark.parametrize("name,seed", sorted(TREES))
def test_default_tree_is_bitwise_the_parents(name, seed):
    _, cs = smoke_cell(CELLS[name])
    assert "model" not in cs.config
    assert digest(weights.make_weights(cs.config, seed)) == TREES[name, seed]


SHAPES = {
    "qwen3-1.7b": {
        "['embed']": (151936, 2048), "['final_norm']": (2048,),
        "['layers']['attn']['k_norm']": (28, 128),
        "['layers']['attn']['q_norm']": (28, 128),
        "['layers']['attn']['wk']": (28, 2048, 1024),
        "['layers']['attn']['wo']": (28, 2048, 2048),
        "['layers']['attn']['wq']": (28, 2048, 2048),
        "['layers']['attn']['wv']": (28, 2048, 1024),
        "['layers']['ln1']": (28, 2048), "['layers']['ln2']": (28, 2048),
        "['layers']['mlp']['w_down']": (28, 6144, 2048),
        "['layers']['mlp']['w_gate']": (28, 2048, 6144),
        "['layers']['mlp']['w_up']": (28, 2048, 6144)},
    "mixtral-8x7b": {
        "['embed']": (32000, 4096), "['final_norm']": (4096,),
        "['layers']['attn']['wk']": (1, 4096, 1024),
        "['layers']['attn']['wo']": (1, 4096, 4096),
        "['layers']['attn']['wq']": (1, 4096, 4096),
        "['layers']['attn']['wv']": (1, 4096, 1024),
        "['layers']['ln1']": (1, 4096), "['layers']['ln2']": (1, 4096),
        "['layers']['moe']['router']": (1, 4096, 8),
        "['layers']['moe']['w_down']": (1, 8, 14336, 4096),
        "['layers']['moe']['w_gate']": (1, 8, 4096, 14336),
        "['layers']['moe']['w_up']": (1, 8, 4096, 14336)},
}


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_default_tree_has_the_parents_shapes_at_published_widths(name):
    conf = _published(name)
    shapes = jax.eval_shape(functools.partial(DECODER.tree, conf),
                            weights.prng_key(1))
    got = {jax.tree_util.keystr(k): (v.shape, v.dtype)
           for k, v in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert got == {k: (s, jnp.dtype("float32"))
                   for k, s in SHAPES[name].items()}


LOGITS = {
    ("qwen3-1.7b", "f32"):
        "54d8c4a79fa26dfe6d98d7d579e8476e29ac422508bfe1cbd437ebe0dece9d04",
    ("qwen3-1.7b", "bf16"):
        "2832740b31946f0a269f1d5c0ccb16f914e970c08cb8f34ef7120c5b5cb9ef71",
    ("qwen3-1.7b", "fp8"):
        "bd18d013094e23c3640add35db47edf6908ac1873b61a00b84d0836aab3017cf",
    ("mixtral-8x7b", "f32"):
        "bfbe5b201dd7880de40371802c323ef150cc6e916dce837b4742ad4f0dee109d",
    ("mixtral-8x7b", "bf16"):
        "85a257ae336158f9e4e5e19a3a6ce89c52b459b2829fb660aca6242a2b09202b",
    ("mixtral-8x7b", "fp8"):
        "9f05599a8adec1c6910b4ac4d78a42935c55f437835b87b462b688b7da5038c9",
}


@pytest.mark.parametrize("name,precision", sorted(LOGITS))
def test_default_reference_logits_are_bitwise_the_parents(name, precision):
    _, cs = smoke_cell(CELLS[name])
    conf = cs.config
    params = weights.make_weights(conf, 5)
    tokens = jnp.asarray(np.arange(2, 40, dtype=np.int32) * 7
                         % conf["vocab_size"])
    out = DECODER.forward(params, tokens, DECODER.model_keys(conf),
                          precision)
    assert digest(out) == LOGITS[name, precision]


# (calls, sum of FLOPs, sum of bytes) of each GEMM list; model FLOPs.
COUNTS = {
    "qwen3-1.7b": {   # the chat cell: 8 slots, view of 1024 keys
        "prefill_model_flops": {1: 3441131520.0, 16: 45750681600.0,
                                257: 732599910400.0, 768: 2233019662336.0,
                                1024: 3007216877568.0},
        "decode_model_flops": {0: 3441131520, 255: 3499622400,
                               1279: 3734503424},
        "prefill_gemms": {(8, 1024): (225, 25018868170752, 38205757440),
                          (8, 32): (225, 786662686720, 5439854592)},
        "decode_gemms": {(8, 1024): (225, 29406265344, 4415920128)}},
    "mixtral-8x7b": {  # the gen-batch cell: 32 slots, view of 1280 keys
        "prefill_model_flops": {1: 1050755072.0, 16: 12881887232.0,
                                257: 203474157568.0, 768: 610740994048.0,
                                1024: 816381427712.0},
        "decode_model_flops": {0: 1050755072, 255: 1054932992,
                               1279: 1071710208},
        "prefill_gemms": {(32, 512): (10, 13203601883136, 10079453184),
                          (32, 64): (10, 1657790267392, 4148477952)},
        "decode_gemms": {(32, 1280): (10, 34294726656, 3349037568)}},
}


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_default_counts_are_the_parents_at_published_widths(name):
    conf, want = _published(name), COUNTS[name]
    for fn in ("prefill_model_flops", "decode_model_flops"):
        for n, value in want[fn].items():
            assert getattr(DECODER, fn)(conf, n) == value, (fn, n)
    for fn in ("prefill_gemms", "decode_gemms"):
        for args, (calls, fl, by) in want[fn].items():
            g = getattr(DECODER, fn)(conf, *args)
            assert (len(g), sum(x for x, _ in g), sum(y for _, y in g)) == (
                calls, fl, by), (fn, args)


def test_model_modules_import_nothing_of_the_program():
    code = (
        "import sys\n"
        "from bench import spec\n"
        "for path in sorted((spec.BENCH_DIR / 'models').glob('*.py')):\n"
        "    spec.model_module({'model': path.stem})\n"
        "    print(path.stem)\n"
        "bad = [m for m in sys.modules if m.split('.')[0] == 'repro']\n"
        "assert not bad, bad\n")
    # The program is importable: a module that imports it is caught in
    # sys.modules, not by an ImportError.
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [str(spec.REPO_DIR), str(spec.REPO_DIR / "src")]))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=spec.REPO_DIR, timeout=300)
    assert p.returncode == 0, p.stderr
    assert "decoder" in p.stdout.split()


PLUG_IN = "mixtral-8x7b.gen-batch"


def _bring_toy(tmp_path, monkeypatch, planted: bool):
    """The toy module as the only model module there is."""
    text = TOY.read_text()
    assert "PLANTED = False" in text
    if planted:
        text = text.replace("PLANTED = False", "PLANTED = True")
    (tmp_path / "toy_decoder.py").write_text(text)
    monkeypatch.setattr(spec, "model_file",
                        lambda name: tmp_path / f"{name}.py")
    return {"model": "toy_decoder"}


def test_configuration_brings_its_own_model_module(measure_smoke, tmp_path,
                                                   monkeypatch):
    config = _bring_toy(tmp_path, monkeypatch, planted=False)
    r = measure_smoke(PLUG_IN, traced=True, limits=LIMITS[PLUG_IN],
                      config=config)
    toy = spec.model_module(config)
    assert r["correct"], r["checks"]
    assert r["window"]["tokens_compared"] >= 40
    assert {"tree", "model_keys", "forward", "warm_insert",
            "prefill_model_flops", "decode_model_flops", "prefill_gemms",
            "decode_gemms"} <= set(toy.CALLS), toy.CALLS
    assert "serve_mfu" in r["metrics"]


def test_wrong_model_forward_reads_incorrect(measure_smoke, tmp_path,
                                             monkeypatch):
    config = _bring_toy(tmp_path, monkeypatch, planted=True)
    r = measure_smoke(PLUG_IN, limits=LIMITS[PLUG_IN], config=config)
    assert spec.model_module(config).PLANTED
    assert not r["correct"], r["checks"]
