"""A configuration names its architecture by its ``model_type``: the program
config, the reference, the weight stds and the counts all come from
``arch/<model_type>.py``, so a new architecture is a new file alone."""
import ast
import hashlib
import json
import textwrap

import jax
import numpy as np
import pytest

import _paths  # noqa: F401

import archs
import check
import run
import server as S
import xplane

DATA = _paths.BENCH / "tests" / "data"
TINY = json.loads((DATA / "tiny.json").read_text())

# a tiny latent-attention (MLA) model: its q_norm and kv_norm leaves are
# 1-D, so only the architecture's STD can give them a standard deviation;
# the reference prefers token 0 everywhere; the counts are constants
TOY = '''
import numpy as np

{std}

def program_config(cfg):
    from repro.configs import MLAConfig, ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=1, d_model=64, n_heads=4,
        n_kv_heads=4, d_ff=128, vocab_size=cfg["vocab_size"],
        mla=MLAConfig(q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8,
                      qk_rope_head_dim=8, v_head_dim=8))
{parallel}

def logits(weights, cfg, tokens, at, quant=None):
    out = np.zeros((len(at), cfg["vocab_size"]), np.float32)
    out[:, 0] = 1.0
    return out


def decode_attention(cfg, ctx_len):
    return 3, 5


def prefill_attention(cfg, start, clen):
    return 7, 11


def token_flops(cfg, ctx_len):
    return 1000
'''
TOY_STD = 'STD = {"q_norm": 0.25, "kv_norm": 0.25}'
TOY_PARALLEL = '''
def parallel_config(cfg):
    from repro.configs import ParallelConfig
    return ParallelConfig(tp=1, dp=1, remat=False, kv_block_size=32)
'''
CFG = {"name": "toy", "model_type": "toy", "vocab_size": 256,
       "serving": TINY["serving"]}


def tree_digest():
    """Every file of the benchmark but compiled bytecode."""
    h = hashlib.sha256()
    for p in sorted(_paths.BENCH.rglob("*")):
        if p.is_file() and "__pycache__" not in p.parts:
            h.update(str(p).encode() + p.read_bytes())
    return h.hexdigest()


@pytest.fixture
def toy(tmp_path, monkeypatch):
    """Writes ``toy.py`` under ``tmp_path``, points the loader there, and
    gives a writer for the toy's variants."""
    monkeypatch.setattr(archs, "ROOT", tmp_path)

    def write(std=TOY_STD, parallel=""):
        path = tmp_path / "toy.py"
        path.write_text(TOY.format(std=std, parallel=parallel))
        archs._load.cache_clear()

    yield write
    archs._load.cache_clear()


@pytest.mark.parametrize("cfg_file", [
    _paths.BENCH / "configs" / "yi-9b-24L.json", DATA / "tiny.json"])
def test_configurations_resolve_to_llama(cfg_file):
    cfg = json.loads(cfg_file.read_text())
    assert cfg["model_type"] == "llama"
    assert archs.find(cfg["model_type"]) == _paths.BENCH / "arch" / "llama.py"
    assert archs.load(cfg).__file__ == str(_paths.BENCH / "arch" / "llama.py")


def test_new_architecture_is_a_file_alone(toy):
    before = tree_digest()
    toy()
    prog = S.program_config(CFG)
    assert prog.name == "toy" and prog.mla.kv_lora_rank == 16
    # no parallel_config in the file: the configuration's serving sizes
    assert S.parallel_config(CFG).kv_block_size == 16

    params = S.draw_weights(CFG, 2**33 + 1, S.make_mesh(CFG))
    paths, _ = jax.tree_util.tree_flatten_with_path(params)
    leaves = {str(p[-1].key): np.asarray(x, np.float32) for p, x in paths}
    assert leaves["q_norm"].shape[-1] == 32
    assert float(leaves["q_norm"].std()) == pytest.approx(0.25, rel=0.5)

    found = check.compare(params, CFG, [{"prompt": [1, 2, 3],
                                         "tokens": [5, 0]}])
    assert found["max_logit_gap"] == 1.0
    assert found["mean_logit_gap"] == 0.5

    dev = xplane.Device("/device:TPU:0", ops=[],
                        modules=[xplane.Event("jit_decode_n(1)", 0.1, 0.2)])
    rec = type("R", (), {"dispatches": [
        {"kind": "decode", "n": 1, "decode_ctx": [[10, 20]], "chunks": []}]})
    ctx = {"cfg": CFG, "peaks": {"bf16_flops_per_s": 1e5}, "chips": 1,
           "recorder": rec, "trace_window": (0.0, 1.0),
           "trace": xplane.Trace([dev], [xplane.Event("chipbench.decode#0",
                                                      0.05, 0.06)])}
    # two decoded tokens of 1000 flops each over 1 s x 1e5 flop/s
    assert run.load_reader("step.mfu").read(ctx) == pytest.approx(2.0)
    assert tree_digest() == before


def test_architecture_may_give_its_parallel_config(toy):
    toy(parallel=TOY_PARALLEL)
    assert S.parallel_config(CFG).kv_block_size == 32


def test_unnamed_one_dimensional_leaf_is_refused(toy):
    toy(std='STD = {"kv_norm": 0.25}')
    with pytest.raises(ValueError, match="'q_norm'"):
        S.draw_weights(CFG, 3, S.make_mesh(CFG))


@pytest.mark.parametrize("model_type", [None, "nosuch"])
def test_missing_architecture_fails_before_weights(tmp_path, model_type):
    cfg = dict(TINY)
    cfg.pop("model_type")
    if model_type:
        cfg["model_type"] = model_type
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "c", "file": "cfg.json"}],
        "workloads": [{"name": "c.w", "config": "c", "traffic": "chat",
                       "chips": 1}],
        "end_to_end": [], "per_layer": []}))
    with pytest.raises(run.BenchError) as e:
        run.load_cell("c.w", False, root=tmp_path / "chipbench")
    looked = archs.ROOT / f"{model_type or '<model_type>'}.py"
    assert "cfg.json" in str(e.value) and str(looked) in str(e.value)


def module_level_imports(src: str):
    """Modules imported when the source is loaded: by every import
    statement but those inside a function's body."""
    todo, names = list(ast.parse(src).body), []
    while todo:
        node = todo.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if isinstance(node, ast.Import):
            names += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            names.append(node.module or "")
        todo.extend(ast.iter_child_nodes(node))
    return names


@pytest.mark.parametrize("path", sorted((_paths.BENCH / "arch").glob("*.py")),
                         ids=lambda p: p.name)
def test_architecture_imports_no_program_at_module_level(path):
    names = module_level_imports(path.read_text())
    assert not [n for n in names if n.split(".")[0] == "repro"], names


def test_import_check_sees_a_module_level_program_import():
    names = module_level_imports(textwrap.dedent("""
        import numpy
        if True:
            from repro.configs import ModelConfig
        def program_config(cfg):
            import repro.models
    """))
    assert sorted(names) == ["numpy", "repro.configs"]
