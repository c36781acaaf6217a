"""The native query framer (native/ingest.cpp ``check_frame_body``) against
the decode it stands in for.

A ``POST /check/batch`` body either DECLINES — and the general decode
serves it, errors included — or it yields query records, flags and
``(sd, tg, multi)`` identical to ``json.loads`` + ``RelationTuple.from_json``
+ the engine's framing loop. Both halves are fuzzed from seeds, and every
malformed request is sent through ``RestApp`` with and without the framer:
status and body must be the same bytes."""

import json
import random

import numpy as np
import pytest

from keto_tpu import namespace as namespace_pkg
from keto_tpu.check import frame as frame_mod
from keto_tpu.check.frame import QueryBatch, QueryFrame
from keto_tpu.check.tpu_engine import TpuCheckEngine
from keto_tpu.config.provider import Config
from keto_tpu.driver.registry import Registry
from keto_tpu.graph.native import FrameTable
from keto_tpu.persistence.memory import MemoryPersister
from keto_tpu.relationtuple.model import RelationTuple, SubjectID, SubjectSet
from keto_tpu.servers import rest
from keto_tpu.servers.rest import READ, RestApp

NAMESPACES = [("docs", 1), ("groups", 2), ("ünï", 7), ("big", 2**40)]
MANAGER = namespace_pkg.MemoryManager(
    [namespace_pkg.Namespace(id=i, name=n) for n, i in NAMESPACES]
)
TABLE = FrameTable.build(MANAGER)

pytestmark = pytest.mark.skipif(TABLE is None, reason="native library not built")

NS_POOL = ["docs", "groups", "ünï", "big", "nope", "", "Docs", "doc"]
OBJECTS = ["o0", "o1", "o2", "", "päper/…", "日本語", "a b\x7f", "😀", "o'\"".replace('"', "”")]
RELATIONS = ["view", "member", "", "édit"]
USERS = ["u0", "u1", "u2", "ghost", "", "ü@example.com", "u:with#marks"]
WS = ["", "", "", " ", "\n", "\t", "\r\n  ", "   "]


def T(ns, obj, rel, sub):
    return RelationTuple(namespace=ns, object=obj, relation=rel, subject=sub)


@pytest.fixture(scope="module")
def engine():
    rng = random.Random(5)
    p = MemoryPersister(MANAGER)
    rows = []
    known = [n for n, _ in NAMESPACES]
    for _ in range(300):
        if rng.random() < 0.5:
            sub = SubjectID(rng.choice(USERS))
        else:
            sub = SubjectSet(rng.choice(known), rng.choice(OBJECTS), rng.choice(RELATIONS))
        rows.append(T(rng.choice(known), rng.choice(OBJECTS), rng.choice(RELATIONS), sub))
    p.write_relation_tuples(*rows)
    eng = TpuCheckEngine(p, MANAGER)
    snap = eng.snapshot()
    if not hasattr(snap.interned, "resolve_queries"):
        pytest.skip("native interner not in use")
    yield eng, snap
    eng.close()


# -- writing bodies by hand: whitespace, key order, raw bytes ---------------------


def jstr(s: str) -> bytes:
    """A JSON string with NO escape: the plain form (the pools hold no
    character that needs one)."""
    assert '"' not in s and "\\" not in s
    return b'"' + s.encode() + b'"'


def element(rng, ns, obj, rel, sid=None, sset=None) -> bytes:
    ws = lambda: rng.choice(WS).encode()  # noqa: E731
    pairs = [(b'"namespace"', jstr(ns)), (b'"object"', jstr(obj)), (b'"relation"', jstr(rel))]
    if sid is not None:
        pairs.append((b'"subject_id"', jstr(sid)))
    if sset is not None:
        inner = [(b'"namespace"', jstr(sset[0])), (b'"object"', jstr(sset[1])),
                 (b'"relation"', jstr(sset[2]))]
        rng.shuffle(inner)
        body = b",".join(ws() + k + ws() + b":" + ws() + v + ws() for k, v in inner)
        pairs.append((b'"subject_set"', b"{" + body + b"}"))
    rng.shuffle(pairs)
    return b"{" + b",".join(ws() + k + ws() + b":" + ws() + v + ws() for k, v in pairs) + b"}"


def random_element(rng) -> bytes:
    ns, obj, rel = rng.choice(NS_POOL), rng.choice(OBJECTS), rng.choice(RELATIONS)
    if rng.random() < 0.55:
        return element(rng, ns, obj, rel, sid=rng.choice(USERS))
    sset = (rng.choice(NS_POOL), rng.choice(OBJECTS), rng.choice(RELATIONS))
    return element(rng, ns, obj, rel, sset=sset)


def wrap(rng, elements) -> bytes:
    ws = lambda: rng.choice(WS).encode()  # noqa: E731
    return (
        ws() + b"{" + ws() + b'"tuples"' + ws() + b":" + ws() + b"["
        + b",".join(ws() + e + ws() for e in elements) + b"]" + ws() + b"}" + ws()
    )


def plain_body(rng, n=None) -> bytes:
    return wrap(rng, [random_element(rng) for _ in range(n or rng.randrange(1, 60))])


GOOD = b'{"namespace":"docs","object":"o0","relation":"view","subject_id":"u0"}'


def one(elem: bytes) -> bytes:
    return b'{"tuples":[' + GOOD + b"," + elem + b"," + GOOD + b"]}"


#: name -> (body, the reason the framer gives); every one of them declines
DECLINES = {
    "empty_body": (b"", None),
    "empty_object": (b"{}", "shape"),
    "empty_array": (b'{"tuples":[]}', "size"),
    "tuples_null": (b'{"tuples":null}', "shape"),
    "tuples_object": (b'{"tuples":{}}', "shape"),
    "top_level_array": (b"[" + GOOD + b"]", "shape"),
    "top_level_extra_key": (b'{"tuples":[' + GOOD + b'],"x":1}', "shape"),
    "top_level_key_first": (b'{"x":1,"tuples":[' + GOOD + b"]}", "shape"),
    "top_level_duplicate": (b'{"tuples":[' + GOOD + b'],"tuples":[' + GOOD + b"]}", "shape"),
    "trailing_garbage": (b'{"tuples":[' + GOOD + b"]} x", "shape"),
    "trailing_comma": (b'{"tuples":[' + GOOD + b",]}", "shape"),
    "bom": (b"\xef\xbb\xbf" + b'{"tuples":[' + GOOD + b"]}", "shape"),
    "utf16": (('{"tuples":[' + GOOD.decode() + "]}").encode("utf-16"), "shape"),
    "element_not_object": (one(b'"docs:o0#view@u0"'), "shape"),
    "element_null": (one(b"null"), "shape"),
    "element_empty": (one(b"{}"), "shape"),
    "missing_namespace": (one(b'{"object":"o","relation":"r","subject_id":"u"}'), "shape"),
    "missing_object": (one(b'{"namespace":"docs","relation":"r","subject_id":"u"}'), "shape"),
    "missing_relation": (one(b'{"namespace":"docs","object":"o","subject_id":"u"}'), "shape"),
    "no_subject": (one(b'{"namespace":"docs","object":"o","relation":"r"}'), "shape"),
    "both_subjects": (one(
        b'{"namespace":"docs","object":"o","relation":"r","subject_id":"u",'
        b'"subject_set":{"namespace":"groups","object":"g","relation":"member"}}'), "shape"),
    "subject_id_null_with_set": (one(
        b'{"namespace":"docs","object":"o","relation":"r","subject_id":null,'
        b'"subject_set":{"namespace":"groups","object":"g","relation":"member"}}'), "shape"),
    "duplicate_key": (one(
        b'{"namespace":"docs","namespace":"groups","object":"o","relation":"r",'
        b'"subject_id":"u"}'), "shape"),
    "unknown_key": (one(
        b'{"namespace":"docs","object":"o","relation":"r","subject_id":"u","extra":"x"}'),
        "shape"),
    "legacy_subject_key": (one(
        b'{"namespace":"docs","object":"o","relation":"r","subject":"u"}'), "shape"),
    "number_value": (one(b'{"namespace":"docs","object":7,"relation":"r","subject_id":"u"}'),
                     "shape"),
    "bool_value": (one(b'{"namespace":true,"object":"o","relation":"r","subject_id":"u"}'),
                   "shape"),
    "null_value": (one(b'{"namespace":"docs","object":"o","relation":null,"subject_id":"u"}'),
                   "shape"),
    "list_value": (one(b'{"namespace":"docs","object":["o"],"relation":"r","subject_id":"u"}'),
                   "shape"),
    "subject_id_number": (one(b'{"namespace":"docs","object":"o","relation":"r","subject_id":5}'),
                          "shape"),
    "subject_set_string": (one(
        b'{"namespace":"docs","object":"o","relation":"r","subject_set":"groups:g#member"}'),
        "shape"),
    "subject_set_missing_key": (one(
        b'{"namespace":"docs","object":"o","relation":"r",'
        b'"subject_set":{"namespace":"groups","object":"g"}}'), "shape"),
    "subject_set_extra_key": (one(
        b'{"namespace":"docs","object":"o","relation":"r",'
        b'"subject_set":{"namespace":"groups","object":"g","relation":"m","x":"y"}}'), "shape"),
    "subject_set_number": (one(
        b'{"namespace":"docs","object":"o","relation":"r",'
        b'"subject_set":{"namespace":2,"object":"g","relation":"m"}}'), "shape"),
    "subject_set_duplicate": (one(
        b'{"namespace":"docs","object":"o","relation":"r",'
        b'"subject_set":{"namespace":"groups","object":"g","object":"h","relation":"m"}}'),
        "shape"),
    "escape_newline": (one(
        b'{"namespace":"docs","object":"a\\nb","relation":"r","subject_id":"u"}'), "escape"),
    "escape_quote": (one(
        b'{"namespace":"docs","object":"a\\"b","relation":"r","subject_id":"u"}'), "escape"),
    "escape_backslash": (one(
        b'{"namespace":"docs","object":"a\\\\b","relation":"r","subject_id":"u"}'), "escape"),
    "escape_unit_separator": (one(
        b'{"namespace":"docs","object":"o\\u001f1\\u001fx","relation":"r","subject_id":"u"}'),
        "escape"),
    "escape_record_separator": (one(
        b'{"namespace":"docs","object":"o","relation":"r","subject_id":"u\\u001e1"}'), "escape"),
    "escape_in_key": (one(
        b'{"name\\u0073pace":"docs","object":"o","relation":"r","subject_id":"u"}'), "escape"),
    "escape_unicode_letter": (one(
        b'{"namespace":"docs","object":"\\u00fc","relation":"r","subject_id":"u"}'), "escape"),
    "raw_unit_separator": (one(
        b'{"namespace":"docs","object":"o\x1fx","relation":"r","subject_id":"u"}'), "encoding"),
    "raw_record_separator": (one(
        b'{"namespace":"docs","object":"o","relation":"r","subject_id":"u\x1e"}'), "encoding"),
    "raw_newline_in_string": (one(
        b'{"namespace":"docs","object":"o\nx","relation":"r","subject_id":"u"}'), "encoding"),
    "raw_nul": (one(
        b'{"namespace":"docs","object":"o\x00","relation":"r","subject_id":"u"}'), "encoding"),
    "utf8_truncated": (one(
        b'{"namespace":"docs","object":"o\xc3","relation":"r","subject_id":"u"}'), "encoding"),
    "utf8_overlong": (one(
        b'{"namespace":"docs","object":"o\xc0\x9f","relation":"r","subject_id":"u"}'),
        "encoding"),
    "utf8_overlong3": (one(
        b'{"namespace":"docs","object":"o\xe0\x80\x9f","relation":"r","subject_id":"u"}'),
        "encoding"),
    "utf8_surrogate": (one(
        b'{"namespace":"docs","object":"o\xed\xa0\x80","relation":"r","subject_id":"u"}'),
        "encoding"),
    "utf8_beyond_unicode": (one(
        b'{"namespace":"docs","object":"o\xf4\x90\x80\x80","relation":"r","subject_id":"u"}'),
        "encoding"),
    "utf8_stray_continuation": (one(
        b'{"namespace":"docs","object":"o\x9f","relation":"r","subject_id":"u"}'), "encoding"),
    "utf8_invalid_lead": (one(
        b'{"namespace":"docs","object":"o\xff","relation":"r","subject_id":"u"}'), "encoding"),
    "latin1": (one(
        '{"namespace":"docs","object":"ü","relation":"r","subject_id":"u"}'.encode("latin-1")),
        "encoding"),
    "unterminated_string": (b'{"tuples":[{"namespace":"docs', "shape"),
    "oversized": (b'{"tuples":[' + b",".join([GOOD] * 9) + b"]}", "size"),
}
#: the cap the oversized body is one over
SMALL_MAX = 8


def frame(body: bytes, max_tuples: int = 65536):
    return TABLE.frame(body, max_tuples) if body else "shape"


def decode(body: bytes) -> list[RelationTuple]:
    return [RelationTuple.from_json(t) for t in json.loads(body)["tuples"]]


def flags_of(n, special, dead, no_target):
    fl = np.zeros(n, np.uint8)
    fl[special], fl[dead], fl[no_target] = frame_mod.SPECIAL, frame_mod.DEAD, frame_mod.NO_TARGET
    return fl


def assert_frame_equals_decode(engine, body: bytes, got) -> None:
    eng, snap = engine
    buf, off, flags = got
    tuples = decode(body)
    n = len(tuples)
    want_buf, special, dead, no_target = eng.dispatch._frame_tuples(snap, tuples)
    assert buf == want_buf
    assert flags.tolist() == flags_of(n, special, dead, no_target).tolist()
    assert off[0] == 0 and off[-1] == len(buf) and len(off) == n + 1
    ends = [i + 1 for i, b in enumerate(buf) if b == 0x1E]
    assert off[1:].tolist() == ends
    framed = QueryFrame(buf, off, flags, body, MANAGER)
    sd, tg, multi = eng.dispatch._resolve_bulk(snap, QueryBatch([(framed, 0, n)]))
    sd_o, tg_o, multi_o = eng.dispatch._resolve_bulk(snap, tuples)
    assert np.array_equal(sd, sd_o) and np.array_equal(tg, tg_o)
    assert multi.keys() == multi_o.keys()
    for i in multi:
        assert np.array_equal(multi[i][0], multi_o[i][0])
        assert np.array_equal(multi[i][1], multi_o[i][1])
    # a cut in the middle resolves to the same rows, with the raw ids of
    # the door (QueryFrame.resolve_at_door) as without them
    for door in (False, True):
        if door:
            framed.resolve_at_door(snap)
            assert framed.door[0] is snap.interned
            sd_d, tg_d, _ = eng.dispatch._resolve_bulk(snap, QueryBatch([(framed, 0, n)]))
            assert np.array_equal(sd_d, sd_o) and np.array_equal(tg_d, tg_o)
        if n > 2:
            a, b = n // 3, n - 1
            sd_c, tg_c, _ = eng.dispatch._resolve_bulk(snap, QueryBatch([(framed, a, b)]))
            assert np.array_equal(sd_c, sd_o[a:b]) and np.array_equal(tg_c, tg_o[a:b])


@pytest.mark.parametrize("seed", range(40))
def test_plain_bodies_frame_to_what_the_decode_gives(engine, seed):
    rng = random.Random(seed)
    body = plain_body(rng)
    got = frame(body)
    assert not isinstance(got, str), got
    assert_frame_equals_decode(engine, body, got)


@pytest.mark.parametrize("name", sorted(DECLINES))
def test_anything_but_the_plain_form_declines(name):
    body, reason = DECLINES[name]
    got = frame(body, SMALL_MAX if name == "oversized" else 65536)
    assert isinstance(got, str), f"{name} was framed"
    if reason is not None:
        assert got == reason


@pytest.mark.parametrize("seed", range(30))
def test_mutated_bodies_decline_or_equal_the_decode(engine, seed):
    """Random damage to a plain body: a byte overwritten, cut out or put
    in, or the body cut short. Whatever still frames is a body the general
    decode accepts, with the same tuples."""
    rng = random.Random(1000 + seed)
    damage = [b"\\", b'"', b"\x1f", b"\x1e", b"{", b"}", b",", b":", b"\xc3", b"\x00", b"[", b"7"]
    for _ in range(60):
        body = bytearray(plain_body(rng, n=rng.randrange(1, 6)))
        pos = rng.randrange(len(body))
        kind = rng.randrange(4)
        if kind == 0:
            body[pos:pos + 1] = rng.choice(damage)
        elif kind == 1:
            del body[pos]
        elif kind == 2:
            body[pos:pos] = rng.choice(damage)
        else:
            del body[pos:]
        body = bytes(body)
        got = frame(body)
        if not isinstance(got, str):
            assert_frame_equals_decode(engine, body, got)


def test_truncated_at_every_byte_never_frames():
    body = plain_body(random.Random(3), n=3)
    for cut in range(len(body.rstrip()) - 1):
        assert isinstance(frame(body[:cut]), str), cut


def test_exactly_the_cap_frames_and_one_more_declines():
    at = b'{"tuples":[' + b",".join([GOOD] * SMALL_MAX) + b"]}"
    got = frame(at, SMALL_MAX)
    assert not isinstance(got, str) and len(got[2]) == SMALL_MAX
    assert frame(DECLINES["oversized"][0], SMALL_MAX) == "size"


def test_a_manager_with_a_wildcard_namespace_gets_no_table():
    nm = namespace_pkg.MemoryManager(
        [namespace_pkg.Namespace(id=1, name="docs"), namespace_pkg.Namespace(id=2, name="")]
    )
    assert FrameTable.build(nm) is None


def test_a_namespace_name_with_a_control_byte_is_never_matched():
    nm = namespace_pkg.MemoryManager(
        [namespace_pkg.Namespace(id=1, name="docs"), namespace_pkg.Namespace(id=2, name="a\x1fb")]
    )
    table = FrameTable.build(nm)
    assert table is not None
    got = table.frame(b'{"tuples":[' + GOOD + b"]}", 10)
    assert got[2].tolist() == [0]
    assert isinstance(table.frame(one(
        b'{"namespace":"a\x1fb","object":"o","relation":"r","subject_id":"u"}'), 10), str)


def test_without_the_native_library_there_is_no_table(monkeypatch):
    from keto_tpu.graph import native

    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_lib_checked", True)
    assert FrameTable.build(MANAGER) is None


# -- through RestApp: a declined body is served exactly as without the framer ------


@pytest.fixture(scope="module")
def apps():
    cfg = Config(overrides={
        "namespaces": [{"id": i, "name": n} for n, i in NAMESPACES],
    })
    reg = Registry(cfg)
    store = reg.relation_tuple_manager()
    store.write_relation_tuples(
        T("docs", "o0", "view", SubjectID("u0")),
        T("docs", "o1", "view", SubjectSet("groups", "g", "member")),
        T("groups", "g", "member", SubjectID("u1")),
        T("ünï", "日本語", "édit", SubjectID("ü@example.com")),
    )
    with_framer = RestApp(reg, READ)
    without = RestApp(reg, READ)
    without._frame_body = lambda scope, body: None  # the parent's path, whole
    yield with_framer, without
    reg.close()


def count(counter, label: str) -> float:
    return sum(v for _n, _ln, labels, v, _e in counter.samples() if labels == (label,))


def post(app, body: bytes):
    status, payload, _headers = app.handle("POST", "/check/batch", {}, body, {})
    return status, json.dumps(payload, sort_keys=True)


@pytest.mark.parametrize("name", sorted(DECLINES))
def test_rest_answers_a_declined_body_as_the_general_path_does(apps, name, monkeypatch):
    with_framer, without = apps
    monkeypatch.setattr(rest, "MAX_BATCH_CHECK", SMALL_MAX)
    body, _reason = DECLINES[name]
    framed_before = count(with_framer._batch_tuples, "framed")
    assert post(with_framer, body) == post(without, body)
    assert count(with_framer._batch_tuples, "framed") == framed_before


#: what the parent answered, letter for letter: every body above that it
#: refused (keto_tpu/servers/rest.py at PR 25, an empty store, this Python)
_CTRL = "Unable to decode JSON payload: Invalid control character at: line 1 column %d (char %d)"
_UTF8 = "'utf-8' codec can't decode byte 0x%s in position %d: invalid %s byte"
PARENT_ERRORS = {
    "both_subjects": (400, "exactly one of subject_set or subject_id has to be provided"),
    "element_empty": (400, "subject is not allowed to be nil"),
    "element_not_object": (400, "expected a JSON object"),
    "element_null": (400, "expected a JSON object"),
    "empty_array": (400, 'expected a non-empty "tuples" array'),
    "empty_body": (400, 'expected a non-empty "tuples" array'),
    "empty_object": (400, 'expected a non-empty "tuples" array'),
    "latin1": (500, _UTF8 % ("fc", 112, "start")),
    "legacy_subject_key": (400, "subject is not allowed to be nil"),
    "no_subject": (400, "subject is not allowed to be nil"),
    "oversized": (400, "too many tuples in one batch check (9 > 8); split the request"),
    "raw_newline_in_string": (400, _CTRL % (114, 113)),
    "raw_nul": (400, _CTRL % (114, 113)),
    "raw_record_separator": (400, _CTRL % (146, 145)),
    "raw_unit_separator": (400, _CTRL % (114, 113)),
    "subject_id_number": (400, "subject_id must be a string"),
    "subject_set_string": (400, "subject_set must be an object"),
    "top_level_array": (400, 'expected a non-empty "tuples" array'),
    "trailing_comma": (
        400, "Unable to decode JSON payload: Expecting value: line 1 column 83 (char 82)"),
    "trailing_garbage": (
        400, "Unable to decode JSON payload: Extra data: line 1 column 85 (char 84)"),
    "tuples_null": (400, 'expected a non-empty "tuples" array'),
    "tuples_object": (400, 'expected a non-empty "tuples" array'),
    "unterminated_string": (
        400, "Unable to decode JSON payload: Unterminated string starting at: "
        "line 1 column 25 (char 24)"),
    "utf8_beyond_unicode": (500, _UTF8 % ("f4", 113, "continuation")),
    "utf8_invalid_lead": (500, _UTF8 % ("ff", 113, "start")),
    "utf8_overlong": (500, _UTF8 % ("c0", 113, "start")),
    "utf8_overlong3": (500, _UTF8 % ("e0", 113, "continuation")),
    "utf8_stray_continuation": (500, _UTF8 % ("9f", 113, "start")),
    "utf8_truncated": (500, _UTF8 % ("c3", 113, "continuation")),
}


@pytest.mark.parametrize("name", sorted(PARENT_ERRORS))
def test_rest_error_texts_are_the_parents(apps, name, monkeypatch):
    with_framer, _ = apps
    monkeypatch.setattr(rest, "MAX_BATCH_CHECK", SMALL_MAX)
    status, payload, _ = with_framer.handle("POST", "/check/batch", {}, DECLINES[name][0], {})
    want_status, want_text = PARENT_ERRORS[name]
    assert (status, payload["error"]["message"]) == (want_status, want_text)


def test_every_refusal_of_the_parent_is_pinned(apps, monkeypatch):
    """Each body above that is not served a 200 has its text written down."""
    _, without = apps
    monkeypatch.setattr(rest, "MAX_BATCH_CHECK", SMALL_MAX)
    refused = {n for n, (body, _r) in DECLINES.items() if post(without, body)[0] != 200}
    assert refused == set(PARENT_ERRORS)


@pytest.mark.parametrize("seed", range(10))
def test_rest_answers_a_framed_body_as_the_general_path_does(apps, seed):
    with_framer, without = apps
    body = plain_body(random.Random(2000 + seed))
    n = len(json.loads(body)["tuples"])
    framed_before = count(with_framer._batch_tuples, "framed")
    got = post(with_framer, body)
    assert got[0] == 200
    assert got == post(without, body)
    assert count(with_framer._batch_tuples, "framed") == framed_before + n


def test_rest_counts_declines_by_reason_and_tuples_by_path(apps):
    with_framer, _ = apps
    m = with_framer.registry.metrics()
    declines = m.family("keto_check_frame_declines_total")
    before = count(declines, "escape")
    objects_before = count(with_framer._batch_tuples, "objects")
    assert post(with_framer, DECLINES["escape_newline"][0])[0] == 200
    assert count(declines, "escape") == before + 1
    assert count(with_framer._batch_tuples, "objects") == objects_before + 3
    text = m.render()
    assert 'keto_check_batch_tuples_total{path="framed"}' in text
    assert "keto_check_frame_materialized_total" in text
