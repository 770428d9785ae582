"""The seam between the session engine and its block kinds: each kind is
a module of ``models/blocks/`` that imports nothing above it, a widths
record is read from ``engine.json`` one way for every kind, and the
records keep the names models persisted before the move pickle them by."""

from __future__ import annotations

import ast
import json
import pathlib

import pytest

from predictionio_tpu.models import seqrec
from predictionio_tpu.models.blocks import (
    common, deepseek_v2, laguna, minicpm_sala, nemotron_h)

BLOCKS_DIR = pathlib.Path(common.__file__).parent

#: record, what engine.json carries, the record built by keyword, and the
#: field that holds a YaRN object (None: the record has none)
WIDTHS = {
    "mla_moe": (
        deepseek_v2.MlaMoeWidths,
        {"experts_held": [4, 4], "num_experts_per_tok": 3,
         "rope_scaling": {"type": "yarn", "factor": 40,
                          "original_max_position_embeddings": 16}},
        deepseek_v2.MlaMoeWidths(
            experts_held=(4, 4), num_experts_per_tok=3,
            rope_scaling=common.YarnScaling(
                factor=40, original_max_position_embeddings=16)),
        "rope_scaling"),
    "sala": (
        minicpm_sala.SalaWidths,
        {"mixer_types": ["minicpm4", "lightning-attn"], "topk": 6},
        minicpm_sala.SalaWidths(mixer_types=("minicpm4", "lightning-attn"),
                                topk=6),
        None),
    "nemotron_h": (
        nemotron_h.NemotronHWidths,
        {"hybrid_override_pattern": "EM*", "experts_held": [0, 64]},
        nemotron_h.NemotronHWidths(hybrid_override_pattern="EM*",
                                   experts_held=(0, 64)),
        None),
    "laguna": (
        laguna.LagunaWidths,
        {"layer_types": ["full_attention", "sliding_attention"],
         "num_attention_heads_per_layer": [48, 72],
         "mlp_layer_types": ["dense", "sparse"], "experts_held": [0, 64],
         "full_rope_scaling": {"factor": 128.0,
                               "original_max_position_embeddings": 8192}},
        laguna.LagunaWidths(
            layer_types=("full_attention", "sliding_attention"),
            num_attention_heads_per_layer=(48, 72),
            mlp_layer_types=("dense", "sparse"), experts_held=(0, 64),
            full_rope_scaling=common.YarnScaling(
                factor=128.0, original_max_position_embeddings=8192)),
        "full_rope_scaling"),
}


@pytest.mark.parametrize("field", list(WIDTHS))
def test_widths_of_reads_what_engine_json_carries(field):
    record, carried, want, yarn = WIDTHS[field]
    got = common.widths_of(record, json.loads(json.dumps(carried)))
    assert got == want and hash(got) == hash(want)
    assert common.widths_of(record, got) is got
    assert common.widths_of(record, None) is None
    if yarn is not None:
        with pytest.raises(ValueError, match="yarn"):
            common.widths_of(record, {yarn: {"type": "linear"}})


def _imports(path: pathlib.Path) -> set[str]:
    names = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            names.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module or "")
            names.update(f"{node.module}.{alias.name}"
                         for alias in node.names)
    return names


def test_no_block_module_imports_what_sits_above_it():
    modules = sorted(BLOCKS_DIR.glob("*.py"))
    assert {m.stem for m in modules} >= set(seqrec.BLOCKS) | {"common"}
    for module in modules:
        above = {name for name in _imports(module)
                 if name.startswith(("predictionio_tpu.models.seqrec",
                                     "predictionio_tpu.templates"))}
        assert not above, (module.name, above)


@pytest.mark.parametrize("name, home", [
    ("YarnScaling", common), ("MlaMoeWidths", deepseek_v2),
    ("SalaWidths", minicpm_sala), ("NemotronHWidths", nemotron_h),
    ("LagunaWidths", laguna)])
def test_persisted_records_keep_their_names_in_seqrec(name, home):
    assert getattr(seqrec, name) is getattr(home, name)
