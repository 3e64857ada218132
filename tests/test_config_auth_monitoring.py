"""Config env parsing, auth composer, adminlist, metrics registry.

Reference test model: usecases/config tests + auth composer/adminlist tests.
"""

import base64
import json

import pytest

from weaviate_tpu.auth import (
    Authenticator,
    Authorizer,
    ForbiddenError,
    UnauthorizedError,
)
from weaviate_tpu.config import ConfigError, load_config
from weaviate_tpu.monitoring import noop_metrics


def test_defaults():
    cfg = load_config({})
    assert cfg.persistence.data_path == "./data"
    assert cfg.auth.anonymous.enabled is True
    assert cfg.query_defaults_limit == 25
    assert cfg.query_maximum_results == 10000
    assert cfg.cluster.gossip_bind_port == 7946
    assert cfg.monitoring.enabled is False


def test_env_surface():
    cfg = load_config({
        "PERSISTENCE_DATA_PATH": "/tmp/w",
        "QUERY_DEFAULTS_LIMIT": "50",
        "QUERY_MAXIMUM_RESULTS": "500",
        "PROMETHEUS_MONITORING_ENABLED": "true",
        "PROMETHEUS_MONITORING_PORT": "9999",
        "CLUSTER_HOSTNAME": "node1",
        "CLUSTER_JOIN": "a:7946, b:7946",
        "ENABLE_MODULES": "text2vec-contextionary,backup-filesystem",
        "DEFAULT_VECTORIZER_MODULE": "text2vec-contextionary",
        "TRACK_VECTOR_DIMENSIONS": "true",
        "GRPC_PORT": "50055",
    })
    assert cfg.persistence.data_path == "/tmp/w"
    assert cfg.query_defaults_limit == 50
    assert cfg.monitoring.enabled and cfg.monitoring.port == 9999
    assert cfg.cluster.join == ["a:7946", "b:7946"]
    assert cfg.enable_modules == ["text2vec-contextionary", "backup-filesystem"]
    assert cfg.track_vector_dimensions is True
    assert cfg.grpc_port == 50055


def test_invalid_int_rejected():
    with pytest.raises(ConfigError):
        load_config({"QUERY_MAXIMUM_RESULTS": "lots"})


def test_apikey_requires_keys_and_users():
    with pytest.raises(ConfigError):
        load_config({"AUTHENTICATION_APIKEY_ENABLED": "true"})
    with pytest.raises(ConfigError):
        load_config({
            "AUTHENTICATION_APIKEY_ENABLED": "true",
            "AUTHENTICATION_APIKEY_ALLOWED_KEYS": "k1,k2",
            "AUTHENTICATION_APIKEY_USERS": "a,b,c",  # mismatch
        })


def _auth_cfg(**env):
    return load_config(env).auth


def test_anonymous_disabled_when_apikey_on():
    cfg = load_config({
        "AUTHENTICATION_APIKEY_ENABLED": "true",
        "AUTHENTICATION_APIKEY_ALLOWED_KEYS": "secret1,secret2",
        "AUTHENTICATION_APIKEY_USERS": "alice,bob",
    })
    a = Authenticator(cfg.auth)
    p = a.principal_from_bearer("secret2")
    assert p.username == "bob"
    with pytest.raises(UnauthorizedError):
        a.principal_from_bearer("wrong")
    with pytest.raises(UnauthorizedError):
        a.principal_from_bearer(None)  # anonymous off by default with apikey on


def test_single_user_for_all_keys():
    cfg = load_config({
        "AUTHENTICATION_APIKEY_ENABLED": "true",
        "AUTHENTICATION_APIKEY_ALLOWED_KEYS": "k1,k2",
        "AUTHENTICATION_APIKEY_USERS": "svc",
    })
    a = Authenticator(cfg.auth)
    assert a.principal_from_bearer("k1").username == "svc"
    assert a.principal_from_bearer("k2").username == "svc"


def test_anonymous_principal():
    a = Authenticator(load_config({}).auth)
    p = a.principal_from_bearer(None)
    assert p.anonymous and p.username == "anonymous"


def test_oidc_fails_closed_without_validator():
    cfg = load_config({
        "AUTHENTICATION_OIDC_ENABLED": "true",
        "AUTHENTICATION_OIDC_ISSUER": "https://issuer",
        "AUTHENTICATION_OIDC_USERNAME_CLAIM": "email",
    })
    claims = base64.urlsafe_b64encode(
        json.dumps({"email": "u@x.io"}).encode()).decode().rstrip("=")
    token = f"h.{claims}.sig"
    # forged/unsigned tokens are rejected unless a validator is wired
    with pytest.raises(UnauthorizedError):
        Authenticator(cfg.auth).principal_from_bearer(token)
    # an explicitly-opted-in unverified validator (dev/test only) parses claims
    a = Authenticator(cfg.auth)
    a.oidc_validator = a.unverified_claims_validator()
    assert a.principal_from_bearer(token).username == "u@x.io"


def test_adminlist():
    cfg = load_config({
        "AUTHORIZATION_ADMINLIST_ENABLED": "true",
        "AUTHORIZATION_ADMINLIST_USERS": "root",
        "AUTHORIZATION_ADMINLIST_READONLY_USERS": "viewer",
    })
    z = Authorizer(cfg.authz)
    from weaviate_tpu.auth.auth import Principal

    z.authorize(Principal("root"), "create", "schema/things")
    z.authorize(Principal("viewer"), "get", "schema/things")
    with pytest.raises(ForbiddenError):
        z.authorize(Principal("viewer"), "create", "schema/things")
    with pytest.raises(ForbiddenError):
        z.authorize(Principal("stranger"), "get", "schema/things")


def test_adminlist_disabled_allows_all():
    from weaviate_tpu.auth.auth import Principal

    z = Authorizer(load_config({}).authz)
    z.authorize(Principal("anyone"), "delete", "objects")  # no raise


def test_metrics_registry_exposition():
    m = noop_metrics()
    m.object_count.labels(class_name="A", shard_name="s0").set(5)
    m.startup_durations.labels(operation="app").observe(1500.0)
    m.vector_index_ops.labels(operation="add", class_name="A", shard_name="s0").inc(3)
    text = m.expose().decode()
    assert 'weaviate_object_count{class_name="A",shard_name="s0"} 5.0' in text
    assert "weaviate_startup_durations_ms_bucket" in text
    assert "weaviate_vector_index_operations_total" in text


def test_every_declared_series_is_observed_by_some_line_of_the_program():
    """A series nothing sets reads as a healthy zero on a dashboard: each
    vec of the registry is named by a line of the package outside its
    declaration (PR 35 took out the fifteen that were not)."""
    import os
    import re

    import weaviate_tpu
    from prometheus_client.metrics import MetricWrapperBase

    pkg = os.path.dirname(weaviate_tpu.__file__)
    source = {}
    for root, _, files in os.walk(pkg):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    source[os.path.join(root, f)] = fh.read()
    declared = os.path.join(pkg, "monitoring", "metrics.py")
    vecs = [a for a, v in vars(noop_metrics()).items()
            if isinstance(v, MetricWrapperBase)]
    assert len(vecs) > 40
    unobserved = []
    for attr in vecs:
        pat = re.compile(r"\b" + attr + r"\b")
        elsewhere = any(pat.search(text) for path, text in source.items()
                        if path != declared)
        # `device_fallbacks` is fed by metrics.py's own helper: a second
        # mention beside the declaration counts
        if not elsewhere and len(pat.findall(source[declared])) < 2:
            unobserved.append(attr)
    assert unobserved == []
    for gone in ("batch_durations", "query_durations", "lsm_compactions",
                 "startup_progress", "schema_tx", "replication_ops"):
        assert gone not in vecs


def test_metrics_isolated_registries():
    m1, m2 = noop_metrics(), noop_metrics()
    m1.object_count.labels(class_name="A", shard_name="s").set(1)
    assert b"weaviate_object_count" not in m2.expose() or \
        b'class_name="A"' not in m2.expose()


def test_vector_index_records_metrics(tmp_path):
    """The TPU index populates the hnsw metrics.go-parity families on
    flush/delete (ops, durations, tombstones, size, dimensions)."""
    import numpy as np

    from weaviate_tpu.entities import vectorindex as vi
    from weaviate_tpu.index.tpu import TpuVectorIndex

    m = noop_metrics()
    cfg = vi.HnswUserConfig.from_dict({"distance": "l2-squared"}, "hnsw_tpu")
    idx = TpuVectorIndex(cfg, str(tmp_path / "C" / "s0"), "s0",
                         metrics=m, persist=False)
    vecs = np.random.default_rng(0).standard_normal((64, 8)).astype(np.float32)
    idx.add_batch(np.arange(64), vecs)
    idx.flush()
    idx.delete(0, 1, 2)
    idx.flush()
    text = m.expose().decode()
    assert 'weaviate_vector_index_operations_total{class_name="C",operation="add",shard_name="s0"} 64.0' in text
    assert 'weaviate_vector_index_tombstones{class_name="C",shard_name="s0"} 3.0' in text
    assert "weaviate_vector_index_durations_ms_bucket" in text
    assert 'weaviate_vector_index_size{class_name="C",shard_name="s0"}' in text
    assert 'weaviate_vector_dimensions_sum{class_name="C",shard_name="s0"}' in text


def test_native_hnsw_records_metrics(tmp_path):
    import numpy as np

    from weaviate_tpu.entities import vectorindex as vi
    from weaviate_tpu.index.hnsw import HnswIndex

    m = noop_metrics()
    cfg = vi.HnswUserConfig.from_dict({"distance": "l2-squared"}, "hnsw")
    idx = HnswIndex(cfg, str(tmp_path / "C" / "s1"), "s1", metrics=m, persist=False)
    vecs = np.random.default_rng(0).standard_normal((50, 8)).astype(np.float32)
    idx.add_batch(np.arange(50), vecs)
    idx.delete(0)
    idx.cleanup_tombstones()
    text = m.expose().decode()
    assert 'weaviate_vector_index_operations_total{class_name="C",operation="add",shard_name="s1"} 50.0' in text
    assert "weaviate_vector_index_tombstone_cleanup_threads_total" in text
