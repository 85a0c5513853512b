"""Config loading, overrides, validation (C9)."""

import pytest

from tpuserve.config import ModelConfig, ServerConfig, default_config, load_config


def test_default_config():
    cfg = default_config()
    assert cfg.port == 8000
    assert cfg.models[0].family == "resnet50"


def test_load_toml(tmp_path):
    p = tmp_path / "serve.toml"
    p.write_text(
        """
port = 9001
decode_threads = 4

[[model]]
name = "rn"
family = "resnet50"
batch_buckets = [1, 8]
deadline_ms = 2.5

[[model]]
name = "bert"
family = "bert"
seq_buckets = [64, 128]
"""
    )
    cfg = load_config(str(p))
    assert cfg.port == 9001
    assert cfg.decode_threads == 4
    assert len(cfg.models) == 2
    assert cfg.model("rn").batch_buckets == [1, 8]
    assert cfg.model("rn").deadline_ms == 2.5
    assert cfg.model("bert").seq_buckets == [64, 128]


def test_overrides(tmp_path):
    p = tmp_path / "serve.toml"
    p.write_text('port = 9001\n[[model]]\nname = "rn"\nfamily = "resnet50"\n')
    cfg = load_config(str(p), overrides=["port=7000", "model.rn.deadline_ms=1.5",
                                         "model.rn.batch_buckets=[2, 4]"])
    assert cfg.port == 7000
    assert cfg.model("rn").deadline_ms == 1.5
    assert cfg.model("rn").batch_buckets == [2, 4]


def test_options_dict_override(tmp_path):
    p = tmp_path / "serve.toml"
    p.write_text('[[model]]\nname = "sd"\nfamily = "sd15"\n')
    cfg = load_config(str(p), overrides=["model.sd.options.num_steps=4"])
    assert cfg.model("sd").options["num_steps"] == 4


def test_pipeline_block(tmp_path):
    p = tmp_path / "pipe.toml"
    p.write_text(
        """
[pipeline]
h2d_workers = 4
depth = 3
arena_slots = 8

[[model]]
name = "rn"
family = "resnet50"
"""
    )
    cfg = load_config(str(p))
    assert cfg.pipeline.h2d_workers == 4
    assert cfg.pipeline.depth == 3
    assert cfg.pipeline.arena_slots == 8
    assert cfg.pipeline.assemble_workers == 2  # default preserved


def test_pipeline_block_validation():
    from tpuserve.config import PipelineConfig

    with pytest.raises(ValueError, match="fetch_workers"):
        PipelineConfig(fetch_workers=0)
    with pytest.raises(ValueError, match=">= 0"):
        PipelineConfig(depth=-1)


def test_unknown_key_rejected(tmp_path):
    p = tmp_path / "bad.toml"
    p.write_text("bogus_key = 1\n")
    with pytest.raises(ValueError, match="unknown"):
        load_config(str(p))


def test_unknown_override_field():
    cfg = ServerConfig(models=[ModelConfig(name="m")])
    with pytest.raises(ValueError, match="unknown config field"):
        load_config_overrides(cfg, "model.m.nope=1")


def load_config_overrides(cfg, ov):
    from tpuserve.config import _apply_override

    _apply_override(cfg, ov)


def test_model_lookup_missing():
    cfg = ServerConfig()
    with pytest.raises(KeyError):
        cfg.model("nope")


def test_import_model_cli_parses_opts(monkeypatch):
    """--opt key=value reaches convert_cli as TOML-typed model options."""
    from tpuserve import cli, savedmodel

    captured = {}
    monkeypatch.setattr(
        savedmodel, "convert_cli",
        lambda sm, fam, out, options=None, quantize=None: captured.update(
            {"sm": sm, "fam": fam, "out": out, "quantize": quantize,
             **(options or {})}))
    rc = cli.main(["import-model", "--saved-model", "x", "--family", "bert",
                   "--out", "y", "--opt", "layers=2",
                   "--opt", "vocab_file=v.txt"])
    assert rc == 0
    assert captured == {"sm": "x", "fam": "bert", "out": "y", "quantize": None,
                        "layers": 2, "vocab_file": "v.txt"}


def test_import_model_cli_rejects_reserved_opts():
    from tpuserve import savedmodel

    with pytest.raises(ValueError, match="weights"):
        savedmodel.convert_cli("sm", "toy", "out", {"weights": "/elsewhere"})


def test_example_serve_all_toml_parses_and_builds():
    """The shipped example config parses, covers all five families, and
    every model in it constructs (no compile — just the family builds)."""
    import os

    from tpuserve.models import build

    path = os.path.join(os.path.dirname(__file__), "..", "examples",
                        "serve_all.toml")
    cfg = load_config(path)
    assert {m.family for m in cfg.models} == {
        "resnet50", "mobilenetv3", "bert", "efficientdet", "sd15"}
    for m in cfg.models:
        build(m)


def test_example_bert_modes_toml_parses_and_builds():
    """The int8-compute example parses and its model constructs with the
    mode wired."""
    import os

    from tpuserve.models import build

    path = os.path.join(os.path.dirname(__file__), "..", "examples",
                        "bert_modes.toml")
    cfg = load_config(path)
    by_name = {m.name: m for m in cfg.models}
    assert by_name["bert-i8c"].quantize == "int8c"
    assert build(by_name["bert-i8c"]).int8c_native_kernel_paths()


def test_warmup_and_describe_cli(tmp_path, capsys):
    """C10: `warmup` builds+compiles from a TOML config and prints the
    runtime inventory; `describe` prints the device/mesh view."""
    import json

    from tpuserve import cli

    toml = tmp_path / "w.toml"
    toml.write_text(
        'port = 18999\n'
        '[[model]]\n'
        'name = "toy"\n'
        'family = "toy"\n'
        'batch_buckets = [1, 2]\n'
        'dtype = "float32"\n'
        'num_classes = 10\n'
        'parallelism = "single"\n'
    )
    assert cli.main(["warmup", "--config", str(toml)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["toy"]["buckets"] == [[1], [2]]
    assert out["toy"]["quantize"] is None

    assert cli.main(["describe"]) == 0
    desc = json.loads(capsys.readouterr().out)
    assert desc["platform"] == "cpu" and len(desc["devices"]) == 8


def test_cache_and_adaptive_blocks(tmp_path):
    p = tmp_path / "demand.toml"
    p.write_text(
        """
[cache]
enabled = true
capacity = 128
ttl_s = 30.0
coalesce = false

[adaptive]
enabled = false
min_target = 2
decrease = 0.25

[[model]]
name = "rn"
family = "resnet50"
"""
    )
    cfg = load_config(str(p))
    assert cfg.cache.enabled is True
    assert cfg.cache.capacity == 128
    assert cfg.cache.ttl_s == 30.0
    assert cfg.cache.coalesce is False
    assert cfg.cache.max_body_bytes == 1048576  # default preserved
    assert cfg.adaptive.enabled is False
    assert cfg.adaptive.min_target == 2
    assert cfg.adaptive.decrease == 0.25
    assert cfg.adaptive.increase == 1.0  # default preserved


def test_cache_and_adaptive_defaults_and_validation():
    from tpuserve.config import AdaptiveConfig, CacheConfig

    cfg = ServerConfig(models=[ModelConfig(name="m")])
    assert cfg.cache.enabled is False  # only deterministic models may opt in
    assert cfg.adaptive.enabled is True
    with pytest.raises(ValueError, match="capacity"):
        CacheConfig(capacity=0)
    with pytest.raises(ValueError, match=">= 0"):
        CacheConfig(ttl_s=-1.0)
    with pytest.raises(ValueError, match="min_target"):
        AdaptiveConfig(min_target=0)
    with pytest.raises(ValueError, match="decrease"):
        AdaptiveConfig(decrease=1.5)
    with pytest.raises(ValueError, match="ewma_alpha"):
        AdaptiveConfig(ewma_alpha=0.0)


def test_parallel_block(tmp_path):
    """[parallel] (ISSUE 7): the multi-chip serving plan parses from TOML
    and from dot-path overrides; invalid modes reject at construction."""
    from tpuserve.config import ParallelConfig

    p = tmp_path / "serve.toml"
    p.write_text(
        """
[parallel]
mode = "replica"
n_chips = 4

[[model]]
name = "rn"
family = "resnet50"
"""
    )
    cfg = load_config(str(p))
    assert cfg.parallel.mode == "replica"
    assert cfg.parallel.n_chips == 4
    assert cfg.parallel.data == 0

    cfg = load_config(str(p), overrides=["parallel.mode=sharded",
                                         "parallel.data=8"])
    assert cfg.parallel.mode == "sharded" and cfg.parallel.data == 8

    # Defaults: per-model parallelism rules, all chips.
    assert ServerConfig().parallel.mode == ""
    with pytest.raises(ValueError, match="parallel.mode"):
        ParallelConfig(mode="pipeline")
    with pytest.raises(ValueError, match="n_chips"):
        ParallelConfig(data=-1)


def test_router_and_worker_blocks(tmp_path):
    """[router]/[worker] (ISSUE 8): the process-split plan parses from TOML
    and dot-path overrides; invalid knobs reject at construction."""
    from tpuserve.config import RouterConfig, WorkerConfig

    p = tmp_path / "serve.toml"
    p.write_text(
        """
[router]
enabled = true
workers = 4
retry_max = 1
hedge_ms = 25.0
respawn_initial_s = 0.25

[worker]
port_base = 9100

[[model]]
name = "rn"
family = "resnet50"
"""
    )
    cfg = load_config(str(p))
    assert cfg.router.enabled and cfg.router.workers == 4
    assert cfg.router.retry_max == 1 and cfg.router.hedge_ms == 25.0
    assert cfg.router.respawn_initial_s == 0.25
    assert cfg.worker.port_base == 9100
    assert cfg.worker.host == "127.0.0.1"

    cfg = load_config(str(p), overrides=["router.workers=8",
                                         "worker.drain_timeout_s=2.5"])
    assert cfg.router.workers == 8
    assert cfg.worker.drain_timeout_s == 2.5

    # Defaults: single-process serving, split off.
    assert ServerConfig().router.enabled is False
    with pytest.raises(ValueError, match="router.workers"):
        RouterConfig(workers=0)
    with pytest.raises(ValueError, match="retry_max"):
        RouterConfig(retry_max=-1)
    with pytest.raises(ValueError, match="respawn"):
        RouterConfig(respawn_multiplier=0.5)
    with pytest.raises(ValueError, match="unhealthy_after"):
        RouterConfig(unhealthy_after=0)
    with pytest.raises(ValueError, match="port_base"):
        WorkerConfig(port_base=-1)


def test_router_hosts_and_routers_knobs(tmp_path):
    """[router] hosts/routers (ISSUE 13): the host failure-domain and
    horizontal-router topology parses, defaults stay flat/single, and
    invalid values reject at construction."""
    from tpuserve.config import RouterConfig

    p = tmp_path / "serve.toml"
    p.write_text(
        """
[router]
enabled = true
hosts = 2
workers = 2
routers = 3
host_breaker_threshold = 5
host_breaker_cooldown_s = 0.5
peer_sync_interval_s = 0.25
peer_port = 9300

[[model]]
name = "rn"
family = "resnet50"
"""
    )
    cfg = load_config(str(p))
    assert cfg.router.hosts == 2 and cfg.router.workers == 2
    assert cfg.router.routers == 3
    assert cfg.router.host_breaker_threshold == 5
    assert cfg.router.host_breaker_cooldown_s == 0.5
    assert cfg.router.peer_sync_interval_s == 0.25
    assert cfg.router.peer_port == 9300

    cfg = load_config(str(p), overrides=["router.hosts=4",
                                         "router.routers=1"])
    assert cfg.router.hosts == 4 and cfg.router.routers == 1

    # Defaults: no host layer, one router — the PR-8 flat topology.
    assert ServerConfig().router.hosts == 0
    assert ServerConfig().router.routers == 1
    with pytest.raises(ValueError, match="hosts"):
        RouterConfig(hosts=-1)
    with pytest.raises(ValueError, match="routers"):
        RouterConfig(routers=0)
    with pytest.raises(ValueError, match="host_breaker"):
        RouterConfig(host_breaker_cooldown_s=0.0)
    with pytest.raises(ValueError, match="peer_sync_interval_s"):
        RouterConfig(peer_sync_interval_s=0.0)
    with pytest.raises(ValueError, match="peer_port"):
        RouterConfig(peer_port=-1)


def test_trace_block(tmp_path):
    p = tmp_path / "trace.toml"
    p.write_text(
        """
[trace]
slow_n = 4
error_capacity = 32
always_record_errors = false
exemplars = false

[[model]]
name = "rn"
family = "resnet50"
"""
    )
    cfg = load_config(str(p))
    assert cfg.trace.slow_n == 4
    assert cfg.trace.error_capacity == 32
    assert cfg.trace.always_record_errors is False
    assert cfg.trace.exemplars is False
    # Defaults + dot-path override.
    cfg2 = load_config(None, overrides=["trace.slow_n=9"])
    assert cfg2.trace.slow_n == 9
    assert cfg2.trace.exemplars is True


def test_trace_block_validation():
    from tpuserve.config import TraceConfig

    with pytest.raises(ValueError, match="slow_n"):
        TraceConfig(slow_n=-1)
    with pytest.raises(ValueError, match="error_capacity"):
        TraceConfig(error_capacity=-1)


def test_events_block(tmp_path):
    p = tmp_path / "events.toml"
    p.write_text(
        """
[events]
capacity = 128
jsonl_path = "/tmp/ev.jsonl"
bridge_level = "WARNING"
dir = "/tmp/bb"
snapshot_interval_s = 0.5
stderr_tail_bytes = 1024
audit_capacity = 32
postmortem_capacity = 8

[[model]]
name = "rn"
family = "resnet50"
"""
    )
    cfg = load_config(str(p))
    assert cfg.events.enabled is True
    assert cfg.events.capacity == 128
    assert cfg.events.jsonl_path == "/tmp/ev.jsonl"
    assert cfg.events.bridge_level == "WARNING"
    assert cfg.events.dir == "/tmp/bb"
    assert cfg.events.snapshot_interval_s == 0.5
    assert cfg.events.stderr_tail_bytes == 1024
    assert cfg.events.audit_capacity == 32
    assert cfg.events.postmortem_capacity == 8
    # Defaults + dot-path override.
    cfg2 = load_config(None, overrides=["events.enabled=false"])
    assert cfg2.events.enabled is False
    assert cfg2.events.capacity == 4096
    assert cfg2.events.stderr_path == "" and cfg2.events.snapshot_path == ""


def test_tenants_block(tmp_path):
    p = tmp_path / "tenants.toml"
    p.write_text(
        """
[tenants]
enabled = true
window_s = 30.0
allow_anonymous = "public"
share_slack = 1.5
slo_latency_ms = 250.0
slo_availability = 0.995
slo_burn_alert = 6.0

[[tenants.tenant]]
name = "acme"
api_key = "acme-key"
weight = 3.0
quota_device_s = 10.0
rate_per_s = 20.0
burst = 40.0

[[tenants.tenant]]
name = "tiny"
api_key = "tiny-key"

[[model]]
name = "rn"
family = "resnet50"
"""
    )
    cfg = load_config(str(p))
    t = cfg.tenants
    assert t.enabled is True
    assert t.window_s == 30.0
    assert t.allow_anonymous == "public"
    assert t.share_slack == 1.5
    assert t.slo_latency_ms == 250.0
    assert t.slo_availability == 0.995
    assert t.slo_burn_alert == 6.0
    assert [x.name for x in t.tenants] == ["acme", "tiny"]
    acme = t.tenants[0]
    assert acme.api_key == "acme-key"
    assert acme.weight == 3.0
    assert acme.quota_device_s == 10.0
    assert acme.rate_per_s == 20.0
    assert acme.burst == 40.0
    # The second entry rides on defaults: weight 1, no envelope.
    assert t.tenants[1].weight == 1.0
    assert t.tenants[1].quota_device_s == 0.0
    # Defaults + dot-path override.
    cfg2 = load_config(None, overrides=["tenants.enabled=true"])
    assert cfg2.tenants.enabled is True
    assert cfg2.tenants.window_s == 60.0
    assert cfg2.tenants.tenants == []


def test_tenants_block_validation(tmp_path):
    from tpuserve.config import TenantConfig, TenantsConfig

    with pytest.raises(ValueError, match="window_s"):
        TenantsConfig(window_s=0.0)
    with pytest.raises(ValueError, match="share_slack"):
        TenantsConfig(share_slack=-1.0)
    with pytest.raises(ValueError, match="slo_latency_ms"):
        TenantsConfig(slo_latency_ms=-1.0)
    with pytest.raises(ValueError, match="slo_availability"):
        TenantsConfig(slo_availability=1.0)
    with pytest.raises(ValueError, match="slo_burn_alert"):
        TenantsConfig(slo_burn_alert=0.0)
    with pytest.raises(ValueError, match="name"):
        TenantConfig(name="", api_key="k")
    with pytest.raises(ValueError, match="api_key"):
        TenantConfig(name="t", api_key="")
    with pytest.raises(ValueError, match="weight"):
        TenantConfig(name="t", api_key="k", weight=0.0)
    with pytest.raises(ValueError, match="quota_device_s"):
        TenantConfig(name="t", api_key="k", quota_device_s=-1.0)
    # Duplicate names/keys are rejected when the TOML list is assembled.
    p = tmp_path / "dup.toml"
    p.write_text(
        """
[tenants]
enabled = true

[[tenants.tenant]]
name = "a"
api_key = "k1"

[[tenants.tenant]]
name = "a"
api_key = "k2"

[[model]]
name = "rn"
family = "resnet50"
"""
    )
    with pytest.raises(ValueError, match="unique"):
        load_config(str(p))


def test_autopilot_block(tmp_path):
    p = tmp_path / "autopilot.toml"
    p.write_text(
        """
[autopilot]
enabled = true
interval_s = 0.25
hysteresis_ticks = 2
cooldown_s = 3.0
max_actions_per_window = 4
window_s = 30.0
follow_up_s = 5.0
rollback_tolerance = 0.25
pressure_high = 1.5
pressure_low = 0.1
clear_high_s = 8.0
min_slots = 2
burn_shed = false
scale = true
paging = true
max_warm = 2
history = 64

[[model]]
name = "rn"
family = "resnet50"
"""
    )
    cfg = load_config(str(p))
    a = cfg.autopilot
    assert a.enabled is True
    assert a.interval_s == 0.25
    assert a.hysteresis_ticks == 2
    assert a.cooldown_s == 3.0
    assert a.max_actions_per_window == 4
    assert a.window_s == 30.0
    assert a.follow_up_s == 5.0
    assert a.rollback_tolerance == 0.25
    assert a.pressure_high == 1.5
    assert a.pressure_low == 0.1
    assert a.clear_high_s == 8.0
    assert a.min_slots == 2
    assert a.burn_shed is False
    assert a.scale is True
    assert a.paging is True
    assert a.max_warm == 2
    assert a.history == 64
    # Defaults + dot-path override.
    cfg2 = load_config(None, overrides=["autopilot.enabled=true"])
    assert cfg2.autopilot.enabled is True
    assert cfg2.autopilot.interval_s == 0.5
    assert cfg2.autopilot.hysteresis_ticks == 3
    assert cfg2.autopilot.paging is False


def test_autopilot_block_validation():
    from tpuserve.config import AutopilotConfig

    with pytest.raises(ValueError, match="interval_s"):
        AutopilotConfig(interval_s=0.0)
    with pytest.raises(ValueError, match="hysteresis_ticks"):
        AutopilotConfig(hysteresis_ticks=0)
    with pytest.raises(ValueError, match="max_actions_per_window"):
        AutopilotConfig(max_actions_per_window=0)
    with pytest.raises(ValueError, match="cooldown_s"):
        AutopilotConfig(cooldown_s=-1.0)
    with pytest.raises(ValueError, match="follow_up_s"):
        AutopilotConfig(follow_up_s=-1.0)
    with pytest.raises(ValueError, match="pressure_low"):
        AutopilotConfig(pressure_low=2.0, pressure_high=1.0)
    with pytest.raises(ValueError, match="min_slots"):
        AutopilotConfig(min_slots=0)


@pytest.mark.parametrize("key, value", [
    ("session_mode", '"recycle"'),
    ("relay_workers", "2"),
    ("relay_epoch_images", "4096"),
    ("relay_epoch_ms", "2000.0"),
    ("relay_slots", "4"),
])
def test_removed_recycle_keys_are_refused(tmp_path, key, value):
    """The deferred-readback execution mode is gone with its five keys: a
    TOML that still sets one fails at load, naming the key, and can never
    come up silently in another mode."""
    p = tmp_path / "old.toml"
    p.write_text(f'[[model]]\nname = "m"\nfamily = "toy"\n{key} = {value}\n')
    with pytest.raises(ValueError, match=f"unknown ModelConfig keys.*{key}"):
        load_config(str(p))
