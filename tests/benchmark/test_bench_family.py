"""The seam between the harness and a model family: a configuration names
its `family`, and `families/<family>.py` + `reference/<family>.py` hold
everything that knows the family's leaves, shapes or arithmetic.

The proof is a second family made of FILES only (`tests/benchmark/llama/`:
`paddle_tpu.models.llama` at `llama_tiny`, 4 query heads on 2 KV heads),
laid over a copy of the tiny tree with list entries beside it and no stock
file edited: one serving and one training cell of it run through
`run_cell` on the CPU, come out correct, read their own counts, and a
planted fault comes out not correct. Beside it, what must not move for
`gpt`: the arrays a seed gives, taken on the parent commit.
"""
import json
import os
import shutil
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

LLAMA = os.path.join(HERE, "llama")
CHAT, TRAIN = "llama-tiny.chat_tiny", "llama-tiny.train_tiny"


def failed(rows):
    return {n for n, v, lim in rows if v is None or not v <= lim}


def llama_tree(tmp_path):
    """A copy of the tiny tree + the llama family's files + its entries
    in the copy's BENCHMARK.json: what a `model_config` PR adds."""
    from _tiny import TINY
    root = tmp_path / "tree"
    shutil.copytree(TINY, root)
    shutil.copytree(LLAMA, root, dirs_exist_ok=True)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "llama-tiny", "source": "test",
        "file": "benchmark/configs/llama-tiny.json", "reduced": [],
        "why": "test"})
    for mix in ("chat_tiny", "train_tiny"):
        bench["workloads"].append({
            "name": f"llama-tiny.{mix}", "config": "llama-tiny",
            "traffic": mix, "chips": 1, "why": "test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if f"gpt-tiny.{mix}" in m.get("workloads", []):
                m["workloads"].append(f"llama-tiny.{mix}")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def cell_of(root, name):
    from _tiny import tiny_cell
    return tiny_cell(name, root=root)


def run(root, name, seed, seconds, traced=False, faults=None):
    from _tiny import run_tiny
    return run_tiny(name, seed, seconds, traced, faults, root=root)


def test_the_tree_edits_no_stock_file_and_finds_its_family(tmp_path):
    root = llama_tree(tmp_path)
    mine = [os.path.relpath(os.path.join(d, f), root)
            for d, _, fs in os.walk(root / "benchmark") for f in fs]
    assert "benchmark/families/llama.py" in mine
    assert not [p for p in mine if os.path.exists(os.path.join(ROOT, p))]
    cell = cell_of(root, CHAT)
    fam, ref = cell.family, cell.reference
    assert fam.__file__ == str(root / "benchmark" / "families" / "llama.py")
    assert ref.__file__ == str(root / "benchmark" / "reference" / "llama.py")
    assert cell.family is fam                       # loaded once a cell
    # the stock family is the stock module, whichever tree asks
    from benchmark.families import gpt
    assert cell_of(root, "gpt-tiny.chat_tiny").family is gpt
    model = cell.config["model"]
    # 2 x (q, o 64x64; k, v 64x32; gate, up, down 64x128) + head 64x256
    assert fam.matmul_params(model) == 2 * (8192 + 4096 + 24576) + 16384
    assert fam.n_params(model) == 90112 + 256 * 64 + (2 * 2 + 1) * 64
    assert fam.kv_bytes_per_token(model) == 2 * 2 * 32 * 2
    assert set(fam.leaf_map(model)) == {
        n for n, _ in fam.build(cell.config).named_parameters()}


def test_llama_reference_agrees_with_the_program(tmp_path):
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from benchmark import system
    cell = cell_of(llama_tree(tmp_path), CHAT)
    fam, ref, model = cell.family, cell.reference, cell.config["model"]
    arrays = fam.make(model, 2**31 + 5, "float32")
    prog = system.build_model(fam, cell.config, arrays)
    prog.eval()
    ids = np.random.default_rng(1).integers(0, 256, (2, 40)).astype("int32")
    with paddle.no_grad():
        got = np.asarray(prog(paddle.to_tensor(ids)).value())
    want = np.asarray(ref.logits(arrays, jnp.asarray(ids), model))
    assert got.shape == want.shape == (2, 40, 256)
    assert np.max(np.abs(got - want)) < 2e-5 * max(1.0, np.abs(want).max())


def test_llama_serving_cell_is_correct_and_reads_its_own_counts(tmp_path):
    from _tiny import PEAKS
    root = llama_tree(tmp_path)
    line, rows, out = run(root, CHAT, 2**31 + 11, 1.5, traced=True)
    assert line["correct"] is True, rows
    assert failed(rows) == set() and line["failed"] == 0
    assert out["numbers"]["tokens_compared"] > 30
    f = out["facts"]
    assert f["counters"][1]["shared_tokens"] == 0        # nothing cached
    done = sum(n for _, first, n, _ in f["flights"] if first is not None)
    pairs = sum(n * (n + 1) // 2 for _, first, n, _ in f["flights"]
                if first is not None)
    steps = [s for s in f["steps"] if s[1] <= f["t_close"]]
    tokens = done + sum(s[2] for s in steps)
    pairs += sum(s[3] for s in steps)
    # llama-tiny: 90,112 parameters a token multiplies, 2 layers of 64
    want = 100.0 * (2.0 * 90112 * tokens + 4.0 * 2 * 64 * pairs) \
        / (f["window_s"] * PEAKS["flops_bf16"])
    got = line["metrics"]["serve_step_mfu.tpot"]["value"]
    assert got == pytest.approx(want, rel=1e-9)
    # gpt's count for the same sizes is another number
    from benchmark.families import gpt
    model = cell_of(root, CHAT).config["model"]
    assert gpt.forward_flops(model, tokens, pairs) != pytest.approx(
        got / 100.0 * f["window_s"] * PEAKS["flops_bf16"], rel=1e-3)


def test_llama_training_cell_is_correct_and_reads_its_own_counts(tmp_path):
    from _tiny import PEAKS
    line, rows, out = run(llama_tree(tmp_path), TRAIN, 7, 1.0, traced=True)
    assert line["correct"] is True, rows
    assert failed(rows) == set() and len(line["compared"]) == 5
    f = out["facts"]
    want = 100.0 * (6.0 * 90112 + 6.0 * 2 * 64 * 128) * f["tokens"] \
        / f["window_s"] / PEAKS["flops_bf16"]
    assert line["metrics"]["train_step_mfu.train"]["value"] == \
        pytest.approx(want, rel=1e-9)


def test_a_zeroed_leaf_of_the_served_model_is_not_correct(tmp_path):
    """The output head zeroed where the engine reads it: every logit is
    0, token 0 is served at every position, and the reference's logit for
    it lies below its best by far more than the limit on every seed and
    at any speed of the host."""
    import jax.numpy as jnp

    def plant(srv):
        head = dict(srv.model.named_parameters())["lm_head.weight"]
        head._data = jnp.zeros_like(head._data)

    line, rows, out = run(llama_tree(tmp_path), CHAT, 5, 1.0,
                          faults={"server": plant})
    assert line["correct"] is False
    assert failed(rows) == {"served_logit_gap"}
    assert out["numbers"]["served_logit_gap"] > 0.1
    assert all(t == 0 for r in out["sample"] for t in r["tokens"])


def test_a_family_without_its_file_names_the_file_to_add(tmp_path):
    root = llama_tree(tmp_path)
    os.remove(root / "benchmark" / "families" / "llama.py")
    with pytest.raises(SystemExit,
                       match=r"benchmark/families/llama\.py is not there"):
        run(root, CHAT, 3, 0.5)
    # the same for a reference, and for a family no tree has
    cfg = root / "benchmark" / "configs" / "llama-tiny.json"
    spec = json.loads(cfg.read_text())
    cfg.write_text(json.dumps(dict(spec, family="mamba")))
    with pytest.raises(SystemExit,
                       match=r"benchmark/reference/mamba\.py is not there"):
        cell_of(root, CHAT).reference
    # and a configuration that names no family is refused as it is loaded:
    # the keys of `model` are never guessed from
    del spec["family"]
    cfg.write_text(json.dumps(spec))
    with pytest.raises(SystemExit, match=r'llama-tiny\.json names no '
                                         r'"family".*families/<family>\.py'):
        cell_of(root, CHAT)


def test_every_configuration_names_a_family_that_has_its_files():
    from benchmark.spec import Cell
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = Cell(w["name"])
        fam, ref = cell.family, cell.reference
        assert fam.__name__ == "benchmark.families." + cell.config["family"]
        for name in ("shapes", "n_params", "make", "build", "leaf_map",
                     "matmul_params", "forward_flops",
                     "train_flops_per_token", "attention_train_flops",
                     "attention_train_bytes", "kv_bytes_per_token",
                     "weight_bytes", "decode_step_bytes"):
            assert callable(getattr(fam, name)), name
        assert set(fam.FUSED) <= set(fam.shapes(cell.config["model"]))
        assert callable(ref.logits) and callable(ref.loss_and_grads)
        assert set(ref.LAYER_KEYS) <= set(fam.shapes(cell.config["model"]))


# ---- what must not move: the arrays a seed gives, as on commit df29a34

# {(seed, dtype): {stacked key: (sum, sum of squares, sum of i x a_i / n)}}
# of `benchmark.weights.make(gpt-tiny's model, seed, dtype)` on df29a34
PARENT_ARRAYS = {
    (3, "float32"): {
        "fc1_b": (-0.5726232454944693, 0.36449483358202384,
                  -0.3492563700423865),
        "fc1_w": (-1.1961998818085675, 52.29573340210345,
                  -1.3180544833058527),
        "fc2_b": (-0.13013772638805676, 0.09911211894291808,
                  0.001391062183699887),
        "fc2_w": (1.6559870433552106, 13.110306937396935,
                  -0.4432065818792721),
        "ln1_b": (0.34521064384898636, 0.09830030086503742,
                  0.13072054456171145),
        "ln1_w": (256.6147538423538, 257.3197435238846, 128.750789742684),
        "ln2_b": (0.5179388934047893, 0.11442029290251521,
                  0.19651500820327783),
        "ln2_w": (255.53675401210785, 255.16461616824364,
                  128.21538151730783),
        "lnf_b": (0.07611485233064741, 0.05102227173104243,
                  0.029008160886405676),
        "lnf_w": (127.80403882265091, 127.65571332078159,
                  64.37293261429295),
        "proj_b": (0.26667752137223744, 0.08456605989916588,
                   0.053516459815802414),
        "proj_w": (-4.609849844535205, 3.3048754275810657,
                   -2.6508748491099343),
        "qkv_b": (0.2855286001049535, 0.29622867871725,
                  -0.08831356033758671),
        "qkv_w": (19.635101571688036, 39.21934343486656,
                  9.106070225080297),
        "wpe": (1.2319817053474935, 6.702544962495468, 0.4261273037423076),
        "wte": (3.804590590308919, 13.184073581236145, 0.27504485521801736),
    },
    (2**31 + 17, "bfloat16"): {
        "fc1_b": (-0.17058932781219482, 0.40930290517236756,
                  -0.1653181577567011),
        "fc1_w": (-1.9834802011027932, 52.530802289530534,
                  -2.674248555157625),
        "fc2_b": (0.2681083679199219, 0.09838128955743741,
                  0.12798871845006943),
        "fc2_w": (-5.552912989631295, 13.123210487580208,
                  -0.8124398824859895),
        "ln1_b": (-0.2003955841064453, 0.09330261269133189,
                  -0.1517830491065979),
        "ln1_w": (255.53125, 255.17379760742188, 128.11187744140625),
        "ln2_b": (0.1789630651473999, 0.11745122250762563,
                  0.17664476158097386),
        "ln2_w": (255.70703125, 255.5059356689453, 128.26290893554688),
        "lnf_b": (0.3288707733154297, 0.05462146624995512,
                  0.15900051593780518),
        "lnf_w": (127.66796875, 127.38401794433594, 64.45379638671875),
        "proj_b": (0.13994823768734932, 0.09965188423872741,
                   0.04691644151171204),
        "proj_w": (1.4065859825350344, 3.2779457912332006,
                   0.19823336074679787),
        "qkv_b": (0.4473065733909607, 0.33218374144046336,
                  0.29906079242937267),
        "qkv_w": (-0.3715004324913025, 39.53316933306589,
                  1.0624086815285712),
        "wpe": (0.41455643996596336, 6.629682336825617,
                -0.8476645770977029),
        "wte": (0.08416611701250076, 12.991911673637336,
                -0.6575419988214435),
    },
}


@pytest.mark.parametrize("seed,dtype", sorted(PARENT_ARRAYS))
def test_the_gpt_family_makes_the_parents_arrays_from_a_seed(seed, dtype):
    """Same keys in the same order under `fold_in`, same recipe: the sums
    of every array equal the ones taken on the parent commit (on this
    installation the arrays are equal bit for bit; the sums are held to
    1e-7 so that another CPU's last ulp of a normal draw is not a
    failure, while a changed key order or scale changes every digit)."""
    from _tiny import tiny_cell
    cell = tiny_cell("gpt-tiny.train_tiny")
    arrays = cell.family.make(cell.config["model"], seed, dtype)
    want = PARENT_ARRAYS[(seed, dtype)]
    assert sorted(arrays) == sorted(want)
    for key, sums in want.items():
        assert str(arrays[key].dtype) == dtype
        a = np.asarray(arrays[key].astype("float32"), np.float64).ravel()
        w = np.arange(1, a.size + 1, dtype=np.float64)
        got = (a.sum(), (a * a).sum(), (a * w).sum() / a.size)
        assert got == pytest.approx(sums, rel=1e-7, abs=1e-7), key


# ---- the two roofline readers that read a family's counts and that no
# CPU run reaches (they need a device's trace): on hand-made traces

class FakeTrainTrace:
    """What `flash_roofline_share` uses of `benchmark.trace.Trace`: two
    runs of the step executable of 0.4 s, 0.1 s of attention kernels."""
    ops = modules = [[1]]
    t0, t1 = 0, 10**9

    def module_runs(self, pattern):
        return [("jit_step_fn", 0, 4 * 10**8), ("jit_step_fn", 5 * 10**8,
                                                9 * 10**8)]

    def op_seconds(self, pattern):
        assert (self.t0, self.t1) == (0, 9 * 10**8)    # the runs' span
        return 0.1, 96


def test_flash_roofline_share_reads_the_familys_attention_counts(tmp_path):
    from _tiny import PEAKS, tiny_cell
    ctx = {"trace": FakeTrainTrace(), "peaks": PEAKS, "facts": {"chips": 1}}
    shares = {}
    for name, cell in (("gpt", tiny_cell("gpt-tiny.train_tiny")),
                       ("llama", cell_of(llama_tree(tmp_path), TRAIN))):
        shares[name] = cell.reader("flash_roofline_share.train")(
            dict(ctx, cell=cell))
    # batch 4 x seq 128, 0.05 s of kernels a step; compute-bound at these
    # peaks: 6 L H S^2 B FLOPs = 6 x 2 x H x 128^2 x 4 over 1e12 FLOP/s
    assert shares["gpt"] == pytest.approx(
        100.0 * 6 * 2 * 128 * 128**2 * 4 / 1e12 / 0.05)
    assert shares["llama"] == pytest.approx(
        100.0 * 6 * 2 * 64 * 128**2 * 4 / 1e12 / 0.05)


def test_decode_hbm_roofline_share_reads_the_familys_bytes(tmp_path):
    """On the trace recorded on a v5e (29 decode runs): fabricated host
    steps of 3 live slots holding 100 tokens, the bytes by each family's
    own count over the mean device time of the decode executable."""
    import gzip
    from jax.profiler import ProfileData
    from _tiny import PEAKS, tiny_cell
    from benchmark import trace as T
    from benchmark.readers import _common
    with gzip.open(os.path.join(HERE, "data", "chat_tiny.xplane.pb.gz")) as f:
        tr = T.Trace(ProfileData.from_serialized_xspace(f.read()).planes)
    ctx = {"trace": tr, "peaks": PEAKS, "host_window": [0.0, 10.0],
           "facts": {"steps": [(1.0, 1.1, 3, 100, 0), (2.0, 2.1, 3, 100, 0),
                               (11.0, 11.1, 4, 400, 0)]}}
    for cell in (tiny_cell("gpt-tiny.chat_tiny"),
                 cell_of(llama_tree(tmp_path), CHAT)):
        fam, model = cell.family, cell.config["model"]
        runs = _common.serve_module_runs(dict(ctx, cell=cell))["decode"]
        nbytes = fam.weight_bytes(model) \
            + fam.kv_bytes_per_token(model) * 103
        want = 100.0 * nbytes / PEAKS["hbm_bytes_per_s"] \
            / (sum(runs) / len(runs))
        got = cell.reader("decode_hbm_roofline_share.tpot")(
            dict(ctx, cell=cell))
        assert got == pytest.approx(want, rel=1e-9) and got > 0
