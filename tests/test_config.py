"""Config provider + namespace watcher.

Covers the reference's config behaviors (reference
internal/driver/config/provider_test.go, namespace_watcher_test.go):
defaults, file/env layering, schema rejection, inline vs URI namespaces,
hot-reload with last-good retention.
"""

import time

import pytest
import yaml

from keto_tpu.config.provider import Config, NamespaceWatcher, load_namespaces_from_uri
from keto_tpu.x.errors import ErrBadRequest, ErrNamespaceUnknown


def test_defaults():
    cfg = Config()
    assert cfg.dsn == "memory"
    assert cfg.read_api_address() == ("", 4466)
    assert cfg.write_api_address() == ("", 4467)
    assert cfg.get("log.level") == "info"
    cfg.close()


def test_file_env_override_layering(tmp_path):
    f = tmp_path / "keto.yml"
    f.write_text(yaml.safe_dump({"serve": {"read": {"port": 1111}}, "log": {"level": "debug"}}))
    cfg = Config(
        config_file=str(f),
        env={"SERVE_READ_PORT": "2222", "DSN": "sqlite://:memory:"},
        overrides={"log.format": "json"},
    )
    # env beats file; explicit overrides beat both
    assert cfg.read_api_address()[1] == 2222
    assert cfg.dsn == "sqlite://:memory:"
    assert cfg.get("log.level") == "debug"
    assert cfg.get("log.format") == "json"
    cfg.close()


def test_schema_rejects_unknown_and_invalid():
    with pytest.raises(ErrBadRequest):
        Config(overrides={"serve.read.port": "not-a-port"})
    with pytest.raises(ErrBadRequest):
        Config(overrides={"nonsense_key": 1})
    with pytest.raises(ErrBadRequest):
        Config(overrides={"log.level": "extreme"})


def test_inline_namespaces():
    cfg = Config(overrides={"namespaces": [{"id": 3, "name": "docs"}]})
    nm = cfg.namespace_manager()
    assert nm.get_namespace_by_name("docs").id == 3
    with pytest.raises(ErrNamespaceUnknown):
        nm.get_namespace_by_name("nope")
    cfg.close()


def test_namespace_uri_file_and_dir(tmp_path):
    (tmp_path / "a.yml").write_text(yaml.safe_dump({"id": 1, "name": "alpha"}))
    (tmp_path / "b.json").write_text('[{"id": 2, "name": "beta"}]')
    nss = load_namespaces_from_uri(f"file://{tmp_path}")
    assert {n.name for n in nss} == {"alpha", "beta"}
    nss = load_namespaces_from_uri(str(tmp_path / "a.yml"))
    assert [n.name for n in nss] == ["alpha"]


def test_watcher_hot_reload_keeps_last_good(tmp_path):
    f = tmp_path / "ns.yml"
    f.write_text(yaml.safe_dump({"id": 1, "name": "one"}))
    w = NamespaceWatcher(str(f), poll_interval=0.05)
    assert w.manager().get_namespace_by_name("one").id == 1

    # valid change is picked up
    f.write_text(yaml.safe_dump([{"id": 1, "name": "one"}, {"id": 2, "name": "two"}]))
    assert w.check_reload() is True
    assert w.manager().get_namespace_by_name("two").id == 2

    # parse error → previous set retained (reference namespace_watcher.go:110-121)
    f.write_text("{definitely: [not, valid")
    assert w.check_reload() is False
    assert w.manager().get_namespace_by_name("two").id == 2
    w.stop()


def test_config_watcher_integration(tmp_path):
    f = tmp_path / "ns.yml"
    f.write_text(yaml.safe_dump({"id": 7, "name": "watched"}))
    cfg = Config(overrides={"namespaces": f"file://{f}"})
    fired = []
    cfg.on_namespace_change(lambda: fired.append(1))
    assert cfg.namespace_manager().get_namespace_by_name("watched").id == 7
    f.write_text(yaml.safe_dump({"id": 8, "name": "watched"}))
    deadline = time.time() + 5
    while time.time() < deadline:
        if fired and cfg.namespace_manager().get_namespace_by_name("watched").id == 8:
            break
        time.sleep(0.05)
    assert cfg.namespace_manager().get_namespace_by_name("watched").id == 8
    assert fired
    cfg.close()


def test_engine_config_keys_are_wired():
    """engine.it_cap reaches the TPU engine; limit.max_read_depth caps
    expand depth at the handler seam (no dead config keys)."""
    from keto_tpu.driver.registry import Registry

    cfg = Config(
        overrides={
            "namespaces": [{"id": 1, "name": "g"}],
            "engine.it_cap": 77,
            "limit.max_read_depth": 3,
        }
    )
    reg = Registry(cfg)
    assert reg.permission_engine().dispatch._it_cap == 77
    # requests asking for 0 or more than the cap get the cap
    assert reg.expand_depth(0) == 3
    assert reg.expand_depth(2) == 2
    assert reg.expand_depth(3) == 3
    assert reg.expand_depth(100) == 3
    cfg.close()


def test_max_read_depth_caps_rest_expand():
    """A deep chain expands only to the configured global depth cap."""
    from keto_tpu.driver.registry import Registry
    from keto_tpu.relationtuple.model import RelationTuple, SubjectID, SubjectSet
    from keto_tpu.servers.rest import RestApp

    cfg = Config(
        overrides={"namespaces": [{"id": 1, "name": "g"}], "limit.max_read_depth": 2}
    )
    reg = Registry(cfg)
    p = reg.relation_tuple_manager()
    p.write_relation_tuples(
        RelationTuple(namespace="g", object="a", relation="m", subject=SubjectSet("g", "b", "m")),
        RelationTuple(namespace="g", object="b", relation="m", subject=SubjectSet("g", "c", "m")),
        RelationTuple(namespace="g", object="c", relation="m", subject=SubjectID("u")),
    )
    status, tree, _ = RestApp(reg, "read").handle(
        "GET", "/expand", {"namespace": ["g"], "object": ["a"], "relation": ["m"], "max-depth": ["50"]}, b""
    )
    assert status == 200
    # depth 2: root union → child b truncated to a leaf (no grandchildren)
    assert tree["type"] == "union"
    child = tree["children"][0]
    assert child["subject_set"]["object"] == "b"
    assert child["type"] == "leaf" and "children" not in child
    cfg.close()


def _wait_for(cond, timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.05)
    return False


def test_websocket_namespace_source():
    """ws:// namespace URI: snapshots push over a live websocket, parse
    errors keep last-good, and the watcher survives a dropped connection
    (reference namespace_watcher.go:47-88 watches file/dir/ws URIs)."""
    from tests.ws_test_server import WsTestServer
    from keto_tpu.config.provider import NamespaceWatcher

    srv = WsTestServer()
    try:
        w = NamespaceWatcher(srv.url, ws_initial_wait=0.1)
        assert srv.wait_client(), "watcher never connected"
        assert w.manager().namespaces() == []

        srv.send_text(yaml.safe_dump([{"id": 1, "name": "alpha"}]))
        assert _wait_for(lambda: [n.name for n in w.manager().namespaces()] == ["alpha"])

        # malformed snapshot → keep last-good
        srv.send_text("{not yaml::")
        srv.send_text(yaml.safe_dump({"id": 2}))  # schema-invalid (no name)
        time.sleep(0.3)
        assert [n.name for n in w.manager().namespaces()] == ["alpha"]

        # update pushes through
        srv.send_text(yaml.safe_dump([{"id": 1, "name": "alpha"}, {"id": 2, "name": "beta"}]))
        assert _wait_for(lambda: len(w.manager().namespaces()) == 2)

        # server drops the connection → watcher reconnects and new
        # snapshots still apply
        srv.drop_client()
        assert srv.wait_client(10), "watcher did not reconnect"
        srv.send_text(yaml.safe_dump([{"id": 9, "name": "gamma"}]))
        assert _wait_for(lambda: [n.name for n in w.manager().namespaces()] == ["gamma"], 10)
        w.stop()
    finally:
        srv.close()


def test_websocket_namespace_source_through_config():
    """Config routes a ws:// namespaces URI through the watcher and fires
    namespace-change callbacks on pushed snapshots."""
    from tests.ws_test_server import WsTestServer

    srv = WsTestServer()
    try:
        cfg = Config(overrides={"namespaces": srv.url})
        fired = []
        cfg.on_namespace_change(lambda: fired.append(1))
        cfg.namespace_manager()  # watcher is constructed lazily
        assert srv.wait_client(), "watcher never connected"
        srv.send_text(yaml.safe_dump([{"id": 4, "name": "pushed"}]))
        assert _wait_for(
            lambda: [n.name for n in cfg.namespace_manager().namespaces()] == ["pushed"]
        )
        assert fired
        cfg.close()
    finally:
        srv.close()


def test_websocket_survives_mid_frame_timeout():
    """Regression: a read timeout while a frame is partially delivered
    must not desynchronize the stream — later snapshots still apply
    (frame parsing is peek-based; no bytes consumed until the whole
    frame is buffered)."""
    import socket as socket_mod
    import struct
    from tests.ws_test_server import WsTestServer
    from keto_tpu.config.provider import NamespaceWatcher

    srv = WsTestServer()
    try:
        w = NamespaceWatcher(srv.url, ws_initial_wait=0.1)
        assert srv.wait_client()
        # deliver one frame split across a >0.5s gap (the watcher's read
        # timeout), header+partial payload first
        payload = yaml.safe_dump([{"id": 1, "name": "slow"}]).encode()
        frame = bytes([0x81, len(payload)]) + payload
        with srv._lock:
            conn = srv._conn
        conn.sendall(frame[:5])
        time.sleep(1.2)  # the watcher times out mid-frame at least once
        conn.sendall(frame[5:])
        assert _wait_for(lambda: [n.name for n in w.manager().namespaces()] == ["slow"])
        # stream must still be in sync: the next snapshot applies too
        srv.send_text(yaml.safe_dump([{"id": 2, "name": "after"}]))
        assert _wait_for(lambda: [n.name for n in w.manager().namespaces()] == ["after"])
        w.stop()
    finally:
        srv.close()


def test_peel_seed_cap_reaches_snapshot_builder():
    """engine.peel_seed_cap plumbs config → engine → build_snapshot (0
    disables peeling entirely; env value coerces to float)."""
    from keto_tpu.config.provider import _coerce
    from keto_tpu.driver.registry import Registry
    from keto_tpu.relationtuple.model import RelationTuple, SubjectID, SubjectSet

    assert _coerce("engine.peel_seed_cap", "2.5") == 2.5
    cfg = Config(overrides={"namespaces": [{"id": 1, "name": "g"}], "engine.peel_seed_cap": 0.0})
    reg = Registry(cfg)
    p = reg.relation_tuple_manager()
    # a chain that peels under the default cap (mid has no sink out-edges)
    p.write_relation_tuples(
        RelationTuple(namespace="g", object="doc", relation="v", subject=SubjectSet("g", "mid", "m")),
        RelationTuple(namespace="g", object="mid", relation="m", subject=SubjectSet("g", "leaf", "m")),
        RelationTuple(namespace="g", object="leaf", relation="m", subject=SubjectID("u")),
    )
    snap = reg.permission_engine().snapshot()
    assert snap.n_peeled == 0, "cap 0 must disable peeling"
    assert reg.permission_engine().subject_is_allowed(
        RelationTuple(namespace="g", object="doc", relation="v", subject=SubjectID("u"))
    )
    cfg.close()
