"""The read side's layers: the model class above its device programs.

``models/word2vec.py`` holds the public model API and its host halves;
everything that is traced lives under ``ops/`` (``ops/scan.py``: the
neighbour and analogy scans and their dispatcher; ``ops/transform.py``: the
sentence slides), as the training steps do under ``train/trainer.select_step``
(PR 57). Held here from the syntax trees, so that the next read operation
adds a program under ``ops/`` and a host half on the model, and from one run
with the retired ``GLINT_CPU_TOPK`` variable set: nothing reads it.
"""

import ast
import os

import jax
import numpy as np
import pytest

PACKAGE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "glint_word2vec_tpu")


def _tree(path: str) -> ast.Module:
    with open(os.path.join(PACKAGE, path)) as f:
        return ast.parse(f.read())


def _dotted(node: ast.AST) -> str:
    """``a.b.c`` of a Name / Attribute chain, "" for anything else."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return ""
    return ".".join([node.id] + parts[::-1])


@pytest.mark.parametrize("module", ["ops/scan.py", "ops/transform.py"])
def test_the_read_programs_import_nothing_above_them(module):
    """Arrows point one way: of the package, ``data/``, ``parallel/`` and
    ``ops/`` alone; never ``models/``, ``serve/``, ``train/`` or ``obs/`` (the
    spans stay in the model's host halves). Imports inside functions count."""
    inside = set()
    for node in ast.walk(_tree(module)):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            # a relative import from ops/<file>: one dot is ops/, two the package
            base = ["glint_word2vec_tpu", "ops"][:3 - node.level] if node.level else []
            name = ".".join(base + ([node.module] if node.module else []))
            names = ([f"{name}.{a.name}" for a in node.names]
                     if name == "glint_word2vec_tpu" else [name])
        else:
            continue
        inside |= {name.split(".")[1] for name in names
                   if name.startswith("glint_word2vec_tpu.")}
    assert inside <= {"data", "parallel", "ops"}, inside


def test_the_model_module_defines_no_device_program():
    """No ``jax.jit``, ``shard_map`` or ``lax`` call or decorator in
    ``models/word2vec.py`` (its docstrings may name them), and no import that
    would bring one in under another name."""
    found = []
    for node in ast.walk(_tree("models/word2vec.py")):
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            module = getattr(node, "module", None) or ""
            for a in node.names:
                if (a.name.split(".")[-1] in ("lax", "jit", "shard_map", "pjit")
                        or "shard_map" in module or module.endswith(".lax")):
                    found.append((node.lineno, f"import {module} {a.name}"))
        name = _dotted(node) if isinstance(node, (ast.Name, ast.Attribute)) else ""
        if (name in ("jit", "shard_map", "lax", "jax.jit", "jax.pjit", "jax.shard_map")
                or name.startswith(("lax.", "jax.lax."))):
            found.append((node.lineno, name))
    assert not found, found


def test_the_host_topk_variable_is_read_by_nothing(monkeypatch):
    """``GLINT_CPU_TOPK=argpartition`` used to send a CPU backend's scores to
    the host to be ranked (PR 57 took the route out): with it set, the scan's
    one program still ranks them, ``_topk_dispatch`` hands back device arrays
    and the replies are the ones without it."""
    import jax.numpy as jnp

    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models import word2vec as w2v

    assert jax.default_backend() == "cpu"
    rng = np.random.default_rng(57)
    syn0 = rng.standard_normal((1500, 16)).astype(np.float32)
    syn0[[7, 300]] = 0.0
    syn0[900] = syn0[4]
    vocab = Vocabulary.from_words_and_counts(
        [f"w{i}" for i in range(1500)], np.ones(1500, np.int64))
    queries = ["w4", "w7", syn0[11] * 2.0, "w1499"]
    model = w2v.Word2VecModel(vocab, jnp.asarray(syn0))
    want = model.find_synonyms_batch(queries, 10)
    handed = []
    real = w2v._topk_dispatch
    monkeypatch.setattr(w2v, "_topk_dispatch",
                        lambda *a: handed.append(real(*a)) or handed[-1])
    monkeypatch.setenv("GLINT_CPU_TOPK", "argpartition")
    got = model.find_synonyms_batch(queries, 10)
    model.stop()
    assert len(handed) == 1 and all(isinstance(a, jax.Array) for a in handed[0])
    assert got == want
