"""The model modules (``bench/models/``): the counts, weights and
reference logits of the grouped-query decoder pinned to the values the
harness gave before it dispatched to a module; an architecture added by
files alone (a toy module, ``tests/toy/interleaved.py``, with a
configuration and a cell written to a temporary directory) driven
through the harness; and a configuration whose ``model`` names no
module, refused."""
import hashlib
import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import check
import flops
import run
import weights
from arch import Arch
from bench_small import arch_of
from repro.configs import get_config

HERE = Path(__file__).resolve().parent
CONFIGS = run.BENCH / "configs"
NAMES = ["internlm2-1.8b", "granite-moe-1b-a400m"]
# read from the harness before the model modules, on the same inputs;
# the logits as a sample (each row's argmax and maximum, and three rows'
# first two and last logits)
PINS = {
    "internlm2-1.8b": {
        "params": 1889110016, "active": 1889110016,
        "decode": (27594326016, 3601305600),
        "prefill": (915225575424, 3429879808),
        "weights": ("d16016018e673b7978ea0edd88bad3c113d69b97cc5d3597cbd082b872dd6e30",
                    "82476bb8a70c5d6c398b2494768d26b3129557cff0c6dbf73b4293b562a335cd"),
        "forward": {
            "argmax": [
                42, 117, 42, 42, 42, 42, 102, 42, 42, 108, 42, 30,
                98, 101, 51, 101, 42, 42, 42, 42, 30, 64, 108, 36],
            "max": [
                0.38879135, 0.3356151, 0.46269205, 0.37476423, 0.51640821, 0.51909119,
                0.52544165, 0.43878815, 0.46060604, 0.3342866, 0.53177088, 0.44829741,
                0.35490742, 0.4269101, 0.46769637, 0.45919508, 0.57670271, 0.40137964,
                0.4572444, 0.39276347, 0.51284713, 0.35266614, 0.35930067, 0.3389689],
            "corners": [
                -0.12820099, -0.18721583, 0.03581889, 0.14741528, -0.27871835, -0.10622327,
                -0.10554257, -0.15475267, 0.22841159]},
        "control": {
            "argmax": [
                42, 19, 42, 42, 42, 42, 42, 42, 42, 108, 42, 30,
                98, 101, 114, 101, 42, 42, 42, 42, 30, 64, 108, 36],
            "max": [
                0.39132044, 0.36945426, 0.46551174, 0.3756139, 0.52003765, 0.5287239,
                0.52520192, 0.45833954, 0.4830586, 0.32456222, 0.54641414, 0.44596988,
                0.34983414, 0.42474213, 0.45122871, 0.44002196, 0.57629853, 0.43182084,
                0.46096459, 0.44228926, 0.52139634, 0.37736401, 0.36305127, 0.36614582],
            "corners": [
                -0.12635335, -0.21455522, 0.0047299699, 0.12028188, -0.2873311, -0.087557882,
                -0.11956469, -0.18712896, 0.24507737]}},
    "granite-moe-1b-a400m": {
        "params": 1334628352, "active": 428658688,
        "decode": (7059062784, 2528464896.0),
        "prefill": (231503370240, 2684616704.0),
        "weights": ("c5fefeedc63646ba2ee27e6da203eb4780dad043846d4e4c5f171ac986fb4d80",
                    "65d360b3918f6491ecdd09d8c08266be6729e3af2e907d02b474fc34d2572ffa"),
        "forward": {
            "argmax": [
                82, 16, 15, 26, 59, 26, 26, 26, 59, 59, 26, 40,
                40, 98, 4, 26, 98, 59, 0, 47, 79, 82, 98, 0],
            "max": [
                0.36210352, 0.34105209, 0.4371509, 0.47400987, 0.35358003, 0.44342297,
                0.4519442, 0.46765402, 0.42928439, 0.49294311, 0.43599769, 0.45997557,
                0.41927797, 0.4327147, 0.37658641, 0.50943094, 0.391507, 0.39793506,
                0.37920147, 0.40104344, 0.40581766, 0.38815254, 0.44799182, 0.32329276],
            "corners": [
                0.30108666, 0.17488565, -0.15155871, 0.21289979, 0.14376453, -0.23505062,
                0.32329276, 0.20048243, -0.22705728]},
        "control": {
            "argmax": [
                82, 65, 15, 26, 26, 26, 26, 26, 30, 59, 26, 40,
                40, 98, 82, 26, 98, 59, 0, 47, 79, 82, 98, 0],
            "max": [
                0.3813822, 0.35583249, 0.41365236, 0.51139247, 0.3429243, 0.4516522,
                0.47863781, 0.47959346, 0.41590711, 0.4856663, 0.46810091, 0.46931854,
                0.43520504, 0.45927596, 0.38366988, 0.57555979, 0.41930366, 0.39179623,
                0.37694541, 0.42774576, 0.38536033, 0.37215364, 0.46198925, 0.33236483],
            "corners": [
                0.2963618, 0.15801592, -0.17225583, 0.21201886, 0.13825989, -0.2329537,
                0.33236483, 0.19077459, -0.21359019]}},
}
SMALL_GAP_LIMIT = 0.05          # as in test_bench_correct.py
# float32 logits of magnitude below 1 (16 ulp and more): the order of a
# reduction may change with XLA's code, any change of the arithmetic
# moves them by far more
LOGIT_ATOL = 1e-6


def digest(tree) -> str:
    """sha256 over every leaf's path, dtype, shape and bytes."""
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}:{x.dtype}:{x.shape}".encode())
        h.update(x.tobytes())
    return h.hexdigest()


def small(name):
    return arch_of(get_config(name).reduced(), slots=2, max_len=64)


@pytest.mark.parametrize("name", NAMES)
def test_counts_at_published_widths_are_pinned(name):
    a = Arch.from_file(CONFIGS / f"{name}.json")
    pin = PINS[name]
    assert a.module.param_count(a.sizes) == pin["params"]
    assert a.module.active_param_count(a.sizes) == pin["active"]
    assert flops.decode(a, [256] * 8) == pin["decode"]
    assert flops.prefill(a, 300) == pin["prefill"]


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("name", NAMES)
def test_weights_are_pinned(name, seed):
    assert digest(weights.make(small(name), seed)) == PINS[name]["weights"][seed]


@pytest.mark.parametrize("quant", [False, True], ids=["forward", "control"])
@pytest.mark.parametrize("name", NAMES)
def test_reference_logits_are_pinned(name, quant):
    a = small(name)
    params = weights.make(a, 0)
    tokens = jnp.asarray(np.random.default_rng(7).integers(0, a.vocab, 24)
                         .astype(np.int32))
    got = a.module.forward(a.sizes, params, tokens, quant)
    assert got.shape == (24, a.vocab) and got.dtype == jnp.float32
    x, pin = np.asarray(got), PINS[name]["control" if quant else "forward"]
    assert x.argmax(1).tolist() == pin["argmax"]
    np.testing.assert_allclose(x.max(1), pin["max"], rtol=0, atol=LOGIT_ATOL)
    corners = [x[i, j] for i in (0, 11, 23) for j in (0, 1, a.vocab - 1)]
    np.testing.assert_allclose(corners, pin["corners"], rtol=0, atol=LOGIT_ATOL)


# every token goes to all four experts: bfloat16 rounding in the program
# cannot flip a routing choice, so the served tokens' gap stays rounding
TOY = {"name": "toy-interleaved", "model": "interleaved", "hidden_act": "silu",
       "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 4,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
       "num_local_experts": 4, "num_experts_per_tok": 4,
       "decoder_sparse_step": 2, "vocab_size": 512, "rope_theta": 10000,
       "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
       "engine": {"slots": 4, "max_len": 1024, "cache_dtype": "float32",
                  "weights_dtype": "float32"}}


def test_an_architecture_added_by_files_alone(tmp_path, monkeypatch):
    """The toy's module, configuration and cell, in a benchmark tree of
    their own (first on the module path, as ``run.py`` puts its own),
    run through the harness as it stands: weights in the
    module's layer tree, the engine serving them, the comparison with the
    module's reference (program tokens within the limit, the control's
    beyond it), and the counts of every call the module's own."""
    bench = tmp_path / "bench"
    for d in ("configs", "models", "cells", "traffic"):
        (bench / d).mkdir(parents=True)
    shutil.copy(HERE / "toy" / "interleaved.py", bench / "models")
    shutil.copy(run.BENCH / "traffic" / "chat-poisson.json", bench / "traffic")
    (bench / "configs" / "toy.json").write_text(json.dumps(TOY))
    (bench / "cells" / "toy-chat.json").write_text(json.dumps(
        {"rate_per_s": 2.0, "logit_gap_limit": SMALL_GAP_LIMIT}))
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "toy", "file": "bench/configs/toy.json"}]
    spec["workloads"] = [{"name": "toy-chat", "config": "toy",
                          "traffic": "chat-poisson", "chips": 1}]

    monkeypatch.setattr(run, "ROOT", tmp_path)
    monkeypatch.setattr(run, "BENCH", bench)
    monkeypatch.syspath_prepend(str(bench))

    cell = run.Cell(spec, "toy-chat")
    assert Path(cell.arch.module.__file__).parent == bench / "models"
    a, seed = cell.arch, 2**33 + 3
    assert a.model == "interleaved" and a.model_config().moe_period == 2
    assert a.module.param_count(a.sizes) == a.model_config().param_count()
    params, engine, _ = run.set_up(cell, seed, trace=False)
    assert [sorted(p) for p in params["layers"]] == [
        ["attn", "mlp", "norm1", "norm2"], ["attn", "moe", "norm1", "norm2"]]
    drive, _, _ = run.measure(cell, engine, seed=seed, seconds=1.5,
                              trace=False, counter=run.CompileCounter())
    rows = check.sample(run.served(drive), seed)
    program, n = check.widest_gap(a, params, rows)
    control, _ = check.widest_gap(a, params, rows, control=True)
    assert n > 0 and program <= SMALL_GAP_LIMIT < control

    work = run.Run(cell=cell, arch=a, drive=drive)
    steps = [st for st in drive.steps if st.decode_lens]
    assert steps and work.work("decode") == tuple(map(sum, zip(*(
        a.module.decode_work(a.sizes, st.decode_lens) for st in steps))))
    prefills = [n for st in drive.steps for n in st.prefills]
    assert prefills and work.work("prefill") == tuple(map(sum, zip(*(
        a.module.prefill_work(a.sizes, n) for n in prefills))))


@pytest.mark.parametrize("model", ["no_such_model", None, "../models/gqa"],
                         ids=["unknown", "none", "not_a_name"])
def test_a_config_that_names_no_model_is_refused(tmp_path, model):
    d = json.loads((CONFIGS / "internlm2-1.8b.json").read_text())
    if model is None:
        del d["model"]
    else:
        d["model"] = model
    path = tmp_path / "config.json"
    path.write_text(json.dumps(d))
    with pytest.raises(ValueError, match='"model"'):
        Arch.from_file(path)
