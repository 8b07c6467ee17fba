"""A configuration's architecture is found from its ``model_type`` in
``arch/<model_type>.py``, and a new one is added by files alone.

The pin (``fixtures/arch_pin.json``) was recorded with ``weights.shapes``,
``weights.make`` and ``reference.decoder`` as they stood before the
lookup: the weights layout of both published configurations, and at the
two small test configurations checksums of the weights, of the reference
logits, of the fp8 control's logits and the bits of the reference loss.
Moving the dense decoder behind the lookup has to reproduce every one.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q
"""

from __future__ import annotations

import ast
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import arch  # noqa: E402
import harness  # noqa: E402
import weights  # noqa: E402
from test_reference import CONFIGS  # noqa: E402

PIN = json.loads((HERE / "tests" / "fixtures" / "arch_pin.json").read_text())
PUBLISHED = ("qwen3-1.7b", "phi3-mini-3.8b")
# matmul parameters per token, as the dense count has always given them
MATMUL_PARAMS = {"qwen3-1.7b": 1_720_451_072, "phi3-mini-3.8b": 1_910_439_936}
GENERIC = ("weights.py", "serving.py", "correctness.py", "readings.py",
           "sweep.py", "harness.py",
           *sorted(f"metrics/{p.name}" for p in (HERE / "metrics").glob("*.py")))


def _published(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def _sha(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(np.asarray(a)).tobytes()).hexdigest()


@pytest.mark.parametrize("name", PUBLISHED)
def test_leaf_table_matches_pin(name):
    cfg = _published(name)
    got = [[k, list(s), list(kind)]
           for k, (s, kind) in sorted(arch.load(cfg).shapes(cfg).items())]
    assert got == PIN["leaf_tables"][name]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_weights_and_reference_match_pin(name):
    cfg = CONFIGS[name]
    pin = PIN["small"][name]
    ref = arch.load(cfg)
    w = weights.make(cfg, 2 ** 31 + 5)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(w):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == pin["weights_sha256"]
    seq = np.random.default_rng(4).integers(0, cfg["vocab_size"], 40).astype(np.int32)
    rows = np.arange(3, 40, 3)
    assert _sha(ref.logits_at(w, seq, rows, cfg)) == pin["logits_sha256"]
    assert _sha(ref.logits_at(w, seq, rows, cfg, "fp8")) == pin["fp8_logits_sha256"]
    toks = jnp.asarray(np.random.default_rng(5).integers(
        0, cfg["vocab_size"], (2, 24)), jnp.int32)
    loss = np.asarray(ref.loss(w, toks, jnp.roll(toks, -1, axis=1), cfg))
    assert loss.astype(np.float32).tobytes().hex() == pin["loss_float32_hex"]


@pytest.mark.parametrize("name", PUBLISHED)
def test_mfu_counts_matmul_params(name):
    cfg = _published(name)
    n = MATMUL_PARAMS[name]
    assert arch.load(cfg).matmul_params_per_token(cfg) == n
    # 3 tokens read back inside a 2 s window, one outside it
    win = SimpleNamespace(cfg=cfg, seconds=2.0, t_open=10.0, t_close=12.0,
                          served=[SimpleNamespace(times=[10.5, 11.0, 13.0]),
                                  SimpleNamespace(times=[11.5])],
                          peaks={"bf16_flops_per_s": 1e14})
    got = harness._reader("mfu.decode").read(win)
    assert got == pytest.approx(100.0 * 2 * n * 3 / 2.0 / 1e14, rel=1e-12)


def test_missing_arch_is_named():
    cfg = dict(CONFIGS["qwen3-like"], model_type="no_such_arch")
    with pytest.raises(arch.MissingArch, match=r"arch/no_such_arch\.py"):
        arch.load(cfg)
    with pytest.raises(arch.MissingArch, match=r"arch/no_such_arch\.py"):
        weights.make(cfg, 1)
    for bad in ({}, {"model_type": "_dense"}, {"model_type": "../weights"}):
        with pytest.raises(arch.MissingArch):
            arch.load(bad)
    assert not issubclass(arch.MissingArch, arch.ProgramFault)


TOY_MOE = '''"""A toy sparse-expert model: embedding; per layer RMSNorm -> the
router's softmax over E SiLU experts, its top k kept and renormalized ->
residual; final RMSNorm; LM head.  No attention."""

import math

import jax
import jax.numpy as jnp

from arch import ProgramFault


def shapes(cfg):
    D, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    E, F = cfg["num_experts"], cfg["moe_intermediate_size"]
    return {
        "embed": ((V, D), ("normal", 0.02)),
        "final_norm.g": ((D,), ("gain",)),
        "lm_head": ((D, V), ("normal", 1 / math.sqrt(D))),
        "layers.norm.g": ((L, D), ("gain",)),
        "layers.moe.router": ((L, D, E), ("normal", 1 / math.sqrt(D))),
        "layers.moe.w_in": ((L, E, D, F), ("normal", 1 / math.sqrt(D))),
        "layers.moe.w_out": ((L, E, F, D), ("normal", 1 / math.sqrt(F))),
    }


def program(cfg, name):
    raise ProgramFault("the program has no toy expert layer")


def check_traffic(cfg, mix, max_len):
    pass


def _rms(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * g


def _logits(w, tokens, cfg):
    k = cfg["num_experts_per_tok"]

    def layer(h, lw):
        x = _rms(h, lw["norm"]["g"])
        p = jax.nn.softmax(x @ lw["moe"]["router"], axis=-1)
        p = jnp.where(p >= jax.lax.top_k(p, k)[0][:, -1:], p, 0.0)
        p = p / p.sum(-1, keepdims=True)
        u = jax.nn.silu(jnp.einsum("td,edf->tef", x, lw["moe"]["w_in"]))
        return h + jnp.einsum("tef,efd,te->td", u, lw["moe"]["w_out"], p), None

    h, _ = jax.lax.scan(layer, w["embed"][tokens], w["layers"])
    return _rms(h, w["final_norm"]["g"]) @ w["lm_head"]


def logits_at(w, tokens, rows, cfg, quant=None):
    if quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    with jax.default_matmul_precision("highest"):
        return _logits(w, jnp.asarray(tokens, jnp.int32), cfg)[jnp.asarray(rows)]


def loss(w, tokens, labels, cfg):
    def one(t, y):
        lg = _logits(w, t, cfg)
        return jnp.mean(jax.nn.logsumexp(lg, -1)
                        - jnp.take_along_axis(lg, y[:, None], -1)[:, 0])

    with jax.default_matmul_precision("highest"):
        return jnp.mean(jax.vmap(one)(tokens, labels))


def matmul_params_per_token(cfg):
    D, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    E, F, k = (cfg["num_experts"], cfg["moe_intermediate_size"],
               cfg["num_experts_per_tok"])
    return L * (D * E + k * 2 * D * F) + D * V
'''

TOY_CONFIG = {"model_type": "toy_moe", "hidden_size": 32, "vocab_size": 64,
              "num_hidden_layers": 2, "num_experts": 4,
              "num_experts_per_tok": 2, "moe_intermediate_size": 16,
              "serve_dtype": "float32"}

# Runs in the copy: the generic modules resolve the added files alone.
DRIVE_TOY = '''
import json
from types import SimpleNamespace
import numpy as np
import arch, correctness, harness, weights

cfg = json.loads((harness.HERE / "configs" / "toy-moe.json").read_text())
mod = arch.load(cfg)
w = weights.make(cfg, 2 ** 31 + 3)
seq = list(np.random.default_rng(0).integers(0, cfg["vocab_size"], 9))
prompt = np.asarray(seq, np.int32)
for _ in range(5):                       # greedy, from the reference
    lg = np.asarray(mod.logits_at(w, np.asarray(seq), [len(seq) - 1], cfg))
    seq.append(int(lg[0].argmax()))
req = SimpleNamespace(rid=0, prompt=prompt, tokens=seq[len(prompt):])
gap, npos = correctness.widest_gap(w, cfg, [req])
bad = SimpleNamespace(rid=1, prompt=prompt,
                      tokens=[(t + 1) % cfg["vocab_size"] for t in req.tokens])
bad_gap, _ = correctness.widest_gap(w, cfg, [bad])
win = SimpleNamespace(cfg=cfg, seconds=1.0, t_open=0.0, t_close=1.0,
                      served=[SimpleNamespace(times=[0.5])],
                      peaks={"bf16_flops_per_s": 1e9})
try:
    mod.program(cfg, "toy")
    fault = None
except arch.ProgramFault as e:
    fault = str(e)
print(json.dumps({
    "router": list(w["layers"]["moe"]["router"].shape),
    "gap": gap, "positions": npos, "bad_gap": bad_gap,
    "mfu": harness._reader("mfu.decode").read(win),
    "module": mod.__file__, "fault": fault}))
'''


@pytest.fixture
def chip_copy(tmp_path):
    """A copy of the benchmark's directory, as a new configuration's PR
    starts from."""
    dst = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, dst, ignore=shutil.ignore_patterns(
        ".cache", ".traces", "__pycache__", "tests"))
    return dst


def _python(cwd: Path, *args: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(cwd))
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_new_architecture_by_files_alone(chip_copy):
    (chip_copy / "arch" / "toy_moe.py").write_text(TOY_MOE)
    (chip_copy / "configs" / "toy-moe.json").write_text(json.dumps(TOY_CONFIG))
    run = _python(chip_copy, "-c", DRIVE_TOY)
    assert run.returncode == 0, run.stderr[-4000:]
    out = json.loads(run.stdout.strip().splitlines()[-1])
    c = TOY_CONFIG
    assert out["module"] == str(chip_copy / "arch" / "toy_moe.py")
    assert out["router"] == [c["num_hidden_layers"], c["hidden_size"],
                             c["num_experts"]]
    assert out["gap"] == 0.0 and out["positions"] == 5
    assert out["bad_gap"] > 0.0
    n = 2 * (32 * 4 + 2 * 2 * 32 * 16) + 32 * 64
    assert out["mfu"] == pytest.approx(100.0 * 2 * n / 1e9, rel=1e-12)
    assert out["fault"] == "the program has no toy expert layer"


def test_missing_arch_fails_before_the_device(chip_copy):
    cfg = dict(_published("qwen3-1.7b"), model_type="ghost_arch")
    (chip_copy / "configs" / "ghost.json").write_text(json.dumps(cfg))
    wl = json.loads((chip_copy / "workloads" / "qwen3-1.7b.chat.json").read_text())
    (chip_copy / "workloads" / "ghost.chat.json").write_text(
        json.dumps(dict(wl, config="ghost")))
    run = _python(chip_copy.parents[1], "benchmarks/chip/harness.py",
                  "--workload", "ghost.chat", "--seed", "1", "--seconds", "1")
    assert run.returncode != 0
    assert "MissingArch" in run.stderr
    assert str(chip_copy / "arch" / "ghost_arch.py") in run.stderr
    assert "no TPU" not in run.stderr and not run.stdout.strip()


def _code_words(path: Path) -> set[str]:
    """Every identifier, attribute, import path and word of a string
    constant in ``path``'s code: docstrings and comments are left out."""
    tree = ast.parse(path.read_text())
    docs = set()
    for node in ast.walk(tree):
        if (isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                              ast.AsyncFunctionDef))
                and node.body and isinstance(node.body[0], ast.Expr)
                and isinstance(node.body[0].value, ast.Constant)):
            docs.add(id(node.body[0].value))
    words = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            words.add(node.id)
        elif isinstance(node, ast.Attribute):
            words.add(node.attr)
        elif isinstance(node, (ast.arg, ast.keyword)) and node.arg:
            words.add(node.arg)
        elif isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            words.add(node.name)
        elif isinstance(node, ast.alias):
            words.update(node.name.split("."))
        elif isinstance(node, ast.ImportFrom) and node.module:
            words.update(node.module.split("."))
        elif (isinstance(node, ast.Constant) and isinstance(node.value, str)
              and id(node) not in docs):
            words.update(re.findall(r"[A-Za-z0-9_]+", node.value))
    return words


def _architecture_words(words: set[str]) -> set[str]:
    leaves = {part for table in PIN["leaf_tables"].values()
              for name, _, _ in table for part in name.split(".")
              if len(part) > 1}
    model_types = {p.stem for p in (HERE / "arch").glob("*.py")
                   if not p.stem.startswith("_")}
    found = words & (leaves | {"dims", "decoder"})
    found |= {w for w in words for t in model_types if t in w.lower()}
    return found


@pytest.mark.parametrize("name", GENERIC)
def test_generic_module_names_no_architecture(name):
    assert _architecture_words(_code_words(HERE / name)) == set()


def test_architecture_scan_finds_what_it_looks_for(tmp_path):
    found = _architecture_words(_code_words(HERE / "arch" / "_dense.py"))
    assert {"dims", "decoder", "wq", "w_gate", "lm_head"} <= found
    src = tmp_path / "m.py"
    src.write_text('"""--workload qwen3-1.7b.chat"""\n'
                   '# phi3\n'
                   'CELL = "phi3-mini-3.8b.chat-closed"\n')
    assert _architecture_words(_code_words(src)) == {"phi3"}
