"""The seeded weights, on the CPU: the rule's weights are pinned to a
checksum, and a configuration's ``seeded_weights`` ``qk_gain`` scales the
query and key projections exactly and nothing else."""
from __future__ import annotations

import dataclasses
import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "tests"), str(BENCH.parent / "src")]

from harness import bench, manifest, weights  # noqa: E402
import tiny  # noqa: E402

SEED = 2**40 + 7
#: sha256 of the tiny qwen configuration's weights at ``SEED``, leaf by
#: leaf (path, dtype, bytes), made by the rule before ``seeded_weights``
#: existed: a configuration without the key gets the same bits
CHECKSUM = "13ae7c50f0e8bbaeb0dde710e99df1614dc2e05ad6689a26147b6ce00357f3b4"


def _leaves(params) -> dict[str, np.ndarray]:
    import jax

    return {jax.tree_util.keystr(path): np.asarray(leaf) for path, leaf
            in jax.tree_util.tree_flatten_with_path(params)[0]}


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    return manifest.resolve("tiny-qwen.tiny",
                            tiny.make(tmp_path_factory.mktemp("perfbench")))


@pytest.fixture(scope="module")
def model(cell):
    return bench.build(cell)


def test_weights_without_the_key_match_the_pinned_checksum(cell, model):
    assert "seeded_weights" not in cell.config
    h = hashlib.sha256()
    for path, a in _leaves(bench.make_weights(cell, model, SEED)).items():
        h.update(path.encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == CHECKSUM


def test_qk_gain_scales_exactly_the_query_and_key_projections(cell, model):
    plain = _leaves(weights.make(model, SEED))
    gained = dataclasses.replace(cell, config=dict(
        cell.config, seeded_weights={"qk_gain": 2.0}))
    got = _leaves(bench.make_weights(gained, model, SEED))
    assert got.keys() == plain.keys()
    changed = {p for p in plain if p.endswith(("['wq']", "['wk']"))}
    assert len(changed) == 2
    for path, a in plain.items():
        want = (a.astype(np.float32) * 2).astype(a.dtype) if path in changed \
            else a
        assert got[path].dtype == a.dtype
        assert got[path].tobytes() == want.tobytes(), path
        if path in changed:
            assert np.abs(a.astype(np.float32)).max() > 0


def test_seeded_weights_come_from_the_configuration_json(tmp_path, model):
    root = tiny.make(tmp_path)
    path = root / "perfbench" / "configs" / "tiny-qwen.json"
    path.write_text(json.dumps(dict(tiny.QWEN,
                                    seeded_weights={"qk_gain": 2.0})))
    cell = manifest.resolve("tiny-qwen.tiny", root)
    got = _leaves(bench.make_weights(cell, model, SEED))
    want = _leaves(weights.make(model, SEED, qk_gain=2.0))
    assert all(got[p].tobytes() == want[p].tobytes() for p in want)


def test_an_unknown_seeded_weights_key_is_refused(cell, model):
    bad = dataclasses.replace(cell, config=dict(
        cell.config, seeded_weights={"gain": 2.0}))
    with pytest.raises(TypeError):
        bench.make_weights(bad, model, SEED)
