"""Native C++ JSONL loader: build, parse, parity with the Python path.

The loader compiles on demand with g++ (present in CI and dev images); if
the toolchain were missing, ``native.available()`` gates every use and the
Python fallback keeps identical semantics — the first test asserts which
world we're in instead of skipping silently.
"""

import json

import pytest

from distributed_llms_example_tpu import native
from distributed_llms_example_tpu.data.dataset import load_json_records

RECORDS = [
    {"dialogue": "plain ascii", "summary": "ok"},
    {"dialogue": 'quotes " and \\ backslash / slash', "summary": "\b\f\n\r\t controls"},
    {"dialogue": "unicode café 日本語", "summary": "astral \U0001f600 emoji"},
    {"dialogue": "numbers", "summary": "x", "id": 17, "score": -3.25e2, "ok": True, "meta": None},
    {"dialogue": "nested", "summary": "y", "tags": ["a", "b"], "extra": {"k": [1, 2]}},
    {},
]


@pytest.fixture(scope="module")
def jsonl_file(tmp_path_factory):
    p = tmp_path_factory.mktemp("jsonl") / "data.jsonl"
    with open(p, "w", encoding="utf-8") as f:
        for r in RECORDS:
            f.write(json.dumps(r) + "\n")
    return str(p)


def test_native_loader_builds():
    assert native.available(), f"native loader failed to build: {native.build_error()}"


def test_native_matches_python(jsonl_file):
    recs = native.load_jsonl(jsonl_file)
    assert len(recs) == len(RECORDS)
    for got, want in zip(recs, RECORDS):
        assert got == want
    assert recs[1:3] == RECORDS[1:3] and recs[:-4] == RECORDS[:-4]  # list-parity slices


def test_escapes_round_trip(tmp_path):
    # ensure the C++ unescaper (not Python's) handles every escape form:
    # write escapes explicitly, including \u-encoded surrogate pairs
    p = tmp_path / "esc.jsonl"
    p.write_text(
        '{"a": "tab\\there", "b": "\\u0041\\u00e9\\u65e5", "c": "\\ud83d\\ude00", "d": "sl\\/ash"}\n',
        encoding="utf-8",
    )
    (rec,) = native.load_jsonl(str(p))
    assert rec == {"a": "tab\there", "b": "Aé日", "c": "\U0001f600", "d": "sl/ash"}


def test_blank_lines_and_missing_trailing_newline(tmp_path):
    p = tmp_path / "gaps.jsonl"
    p.write_text('{"a": "1"}\n\n  \n{"a": "2"}', encoding="utf-8")
    recs = native.load_jsonl(str(p))
    assert [r["a"] for r in recs] == ["1", "2"]


def test_lone_surrogates_rejected_at_parse(tmp_path):
    """Lone \\u surrogates (either half) must fail at LOAD time — past
    load, the Python fallback can no longer engage and the bad bytes would
    surface as UnicodeDecodeError mid-training."""
    for esc in ("\\ud800", "\\udc00"):
        p = tmp_path / "lone.jsonl"
        p.write_text('{"a": "bad %s"}\n' % esc, encoding="utf-8")
        with pytest.raises(ValueError, match="surrogate"):
            native.load_jsonl(str(p))


def test_invalid_utf8_rejected_at_parse(tmp_path):
    """A stray non-UTF-8 byte in a string value must fail at LOAD time
    (clean fallback window), not as UnicodeDecodeError at access time."""
    p = tmp_path / "latin1.jsonl"
    p.write_bytes(b'{"a": "caf\xe9"}\n')  # latin-1 e-acute, invalid UTF-8
    with pytest.raises(ValueError, match="UTF-8"):
        native.load_jsonl(str(p))


def test_negative_indexing_matches_list(jsonl_file):
    recs = native.load_jsonl(jsonl_file)
    assert recs[-1] == RECORDS[-1]
    with pytest.raises(IndexError):
        recs[-len(RECORDS) - 1]


def test_malformed_reports_line(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text('{"a": "ok"}\n{"a": nope}\n', encoding="utf-8")
    with pytest.raises(ValueError, match="line 2"):
        native.load_jsonl(str(p))


def test_load_json_records_routes_jsonl_natively(jsonl_file):
    recs = load_json_records(jsonl_file)
    if native.available():
        assert isinstance(recs, native.JsonlRecords)
    assert list(recs) == RECORDS


def test_load_json_records_python_fallback_parity(jsonl_file, monkeypatch):
    monkeypatch.setenv("DLLM_NATIVE_JSONL", "0")
    recs = load_json_records(jsonl_file)
    assert not isinstance(recs, native.JsonlRecords)
    assert list(recs) == RECORDS


def test_data_wrapper_still_works(tmp_path):
    # single {"data": [...]} object is not JSONL; the native parser must
    # reject it cleanly and the Python path must take over
    p = tmp_path / "wrap.json"
    p.write_text(json.dumps({"data": [{"dialogue": "d", "summary": "s"}]}, indent=2))
    recs = load_json_records(str(p))
    assert list(recs) == [{"dialogue": "d", "summary": "s"}]


def test_dataset_over_native_records(jsonl_file):
    """The lazy dataset consumes the lazy native sequence directly."""
    from distributed_llms_example_tpu.data.dataset import SummarizationDataset
    from distributed_llms_example_tpu.data.tokenizer import get_tokenizer

    recs = load_json_records(jsonl_file)
    ds = SummarizationDataset(recs, get_tokenizer("byte", ""))
    ex = ds[0]
    assert ex.input_ids and ex.labels
