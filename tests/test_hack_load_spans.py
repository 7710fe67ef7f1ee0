"""``hack/load_spans.py --gru-tail``: the GRU tail's pieces timed from
outside on both paths, at toy size, so the tool cannot rot (ISSUE 46)."""

import importlib.util
import os

import pytest

from dragonfly2_tpu.schema import native, wire

ROOT = os.path.normpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


@pytest.fixture(scope="module")
def load_spans():
    spec = importlib.util.spec_from_file_location("hack_load_spans", os.path.join(ROOT, "hack", "load_spans.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def upload(load_spans, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("upload") / "upload.dfb")
    load_spans.stage(path, chunks=2, bodies=2, body_records=512, seed=7)  # eight blocks
    return path


@pytest.mark.parametrize("beside", [0, 2])
@pytest.mark.parametrize("library", [True, False])
def test_a_reading_tells_the_tail_and_its_pieces_apart(load_spans, upload, monkeypatch, library, beside):
    monkeypatch.setenv("DF_NO_NATIVE", "")  # the tool sets or clears it for its reading: as it was again at teardown
    if library and native.load() is None:
        pytest.skip("native library unavailable (no toolchain)")
    held = [len(cols["gru.sequences"]) for _, cols in wire.iter_blocks(upload, columns=("gru.sequences",))]
    cap = sum(held[-3:]) - 1  # held by the last three blocks of eight
    line = load_spans.tail(upload, cap, beside, 0.03, library)
    assert line["library"] is library and line["sequences"] == cap and (line["decoded"], line["hopped"]) == (3, 5)
    assert line["calls"] == ({"check": 1, "copies": 3, "headers": 3, "hop": 2} if library else {"check": 3, "copies": 3, "headers": 3, "hop": 1})
    assert line["told"] == int(library) and (line["told_native_s"] > 0) == library
    pieces = [line[f"{piece}_s"] for piece in ("hop", "headers", "check", "copies")]
    assert all(s >= 0 for s in pieces) and sum(pieces) <= line["tail_s"] and line["rest_s"] >= 0
    # the module's names are its own again
    assert wire.json.__name__ == "json" and wire.zlib.__name__ == "zlib" and wire.np.__name__ == "numpy"
    assert wire._hop_mapped.__name__ == "_hop_mapped" and native.load.__module__ == native.__name__
