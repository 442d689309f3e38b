"""Tests for MeshConfig rank derivation + YAML loading (reference
``config/cache_config.py:20-76`` semantics)."""

import pytest

from radixmesh_tpu.config import MeshConfig, NodeRole, load_config, parse_addr


def cluster(local="p0"):
    return MeshConfig(
        prefill_nodes=["p0", "p1", "p2"],
        decode_nodes=["d0", "d1"],
        router_nodes=["r0"],
        local_addr=local,
    )


class TestRanks:
    def test_rank_space(self):
        cfg = cluster()
        assert cfg.num_prefill == 3 and cfg.num_decode == 2 and cfg.num_ring == 5
        assert [cfg.role_of_rank(r) for r in range(6)] == [
            NodeRole.PREFILL,
            NodeRole.PREFILL,
            NodeRole.PREFILL,
            NodeRole.DECODE,
            NodeRole.DECODE,
            NodeRole.ROUTER,
        ]

    def test_local_identity(self):
        assert cluster("p1").local_identity() == (NodeRole.PREFILL, 1, 1)
        assert cluster("d0").local_identity() == (NodeRole.DECODE, 3, 0)
        assert cluster("r0").local_identity() == (NodeRole.ROUTER, 5, 0)

    def test_addr_lookup(self):
        cfg = cluster()
        assert cfg.prefill_addr(2) == "p2"
        assert cfg.decode_addr(4) == "d1"
        assert cfg.addr_of_rank(5) == "r0"

    def test_membership_enforced(self):
        with pytest.raises(ValueError):
            cluster("nope").local_identity()

    def test_multi_router_accepted_with_real_validation(self):
        """The single-router cap is gone (multi-router front door):
        N distinct routers validate, and the global rank space accounts
        for every one of them."""
        cfg = cluster()
        cfg.router_nodes = ["r0", "r1"]
        cfg.validate()
        assert cfg.num_total == cfg.num_ring + 2
        assert cfg.is_router_rank(cfg.num_ring)
        assert cfg.is_router_rank(cfg.num_ring + 1)
        assert cfg.addr_of_rank(cfg.num_ring + 1) == "r1"
        # Role-consistent identity for the second router.
        cfg2 = cluster()
        cfg2.router_nodes = ["r0", "r1"]
        cfg2.local_addr = "r1"
        assert cfg2.local_identity() == (NodeRole.ROUTER, cfg2.num_ring + 1, 1)

    def test_multi_router_duplicate_rejected(self):
        cfg = cluster()
        cfg.router_nodes = ["r0", "r0"]
        with pytest.raises(ValueError):
            cfg.validate()

    def test_multi_router_empty_addr_rejected(self):
        cfg = cluster()
        cfg.router_nodes = ["r0", ""]
        with pytest.raises(ValueError):
            cfg.validate()

    def test_rebalance_requires_sharding(self):
        cfg = cluster()
        cfg.rebalance_interval_s = 1.0
        with pytest.raises(ValueError):
            cfg.validate()
        cfg.replication_factor = 2
        cfg.validate()

    def test_duplicate_addr_rejected(self):
        cfg = cluster()
        cfg.decode_nodes = ["p0", "d1"]
        with pytest.raises(ValueError):
            cfg.validate()


class TestYaml:
    def test_load(self, tmp_path):
        p = tmp_path / "node.yaml"
        p.write_text(
            """
prefill_nodes: ["localhost:50000", "localhost:50001"]
decode_nodes: ["localhost:50003"]
router_nodes: ["localhost:50010"]
local_addr: "localhost:50001"
protocol: inproc
page_size: 1
num_kv_slots: 1024
"""
        )
        cfg = load_config(str(p))
        assert cfg.local_identity() == (NodeRole.PREFILL, 1, 1)
        assert cfg.num_kv_slots == 1024
        assert cfg.protocol == "inproc"

    def test_unknown_key_rejected(self, tmp_path):
        p = tmp_path / "bad.yaml"
        p.write_text(
            """
prefill_nodes: ["a"]
decode_node: ["b"]
local_addr: "a"
"""
        )
        with pytest.raises(ValueError, match="unknown config keys"):
            load_config(str(p))

    def test_parse_addr(self):
        assert parse_addr("localhost:50000") == ("localhost", 50000)
        assert parse_addr("10.0.0.1:99") == ("10.0.0.1", 99)


class TestRemovedDecodeOptions:
    """One decode step is one launch: the two options that selected
    another (multi-step launches, the paged/dense crossover) are gone,
    and a command line or a YAML that still names one fails on it."""

    @pytest.mark.parametrize(
        "flag", ["--decode-steps-per-launch", "--paged-min-batch"]
    )
    def test_serve_rejects_the_flag(self, flag, capsys):
        from radixmesh_tpu import launch

        with pytest.raises(SystemExit) as e:
            launch.main(["serve", "--model", "llama3-tiny", flag, "2"])
        assert e.value.code == 2
        assert f"unrecognized arguments: {flag}" in capsys.readouterr().err

    @pytest.mark.parametrize(
        "key", ["decode_steps_per_launch", "paged_min_batch"]
    )
    def test_yaml_model_key_refused_by_name(self, key, tmp_path):
        p = tmp_path / "old.yaml"
        p.write_text(
            f"""
prefill_nodes: ["localhost:50000"]
local_addr: "localhost:50000"
protocol: inproc
model:
  preset: llama3-tiny
  max_batch: 4
  {key}: 8
"""
        )
        with pytest.raises(ValueError, match=f"unknown model keys.*{key}"):
            load_config(str(p))

    def test_every_model_key_launch_reads_is_known(self):
        import inspect
        import re

        from radixmesh_tpu import launch
        from radixmesh_tpu.config import MODEL_KEYS

        read = set(re.findall(
            r"model(?: or \{\}\))?\.get\(\s*\"(\w+)\"", inspect.getsource(launch)
        ))
        assert len(read) > 15 and read <= MODEL_KEYS, read - MODEL_KEYS
