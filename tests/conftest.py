"""Tests run on the CPU with 4 devices, so a plane of one-device
replicas gets a device each (the 512-device XLA_FLAGS override belongs
ONLY to the dry-run)."""
import os

# keep any externally-set XLA_FLAGS from leaking a device-count override
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" in flags:
    os.environ["XLA_FLAGS"] = " ".join(
        f for f in flags.split() if "host_platform_device_count" not in f)

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platform_name", "cpu")
jax.config.update("jax_num_cpu_devices", 4)


def pytest_configure(config):
    # the TPU library admits one process per machine, so the tests that
    # describe a TPU topology share one xdist worker through their
    # ``xdist_group``; plain ``-n N`` ("load") would ignore the group,
    # and "loadgroup" is "load" that honours it
    if getattr(config.option, "dist", "no") == "load":
        config.option.dist = "loadgroup"
    # a worker parses the unpromoted arguments, so the controller tells it
    if getattr(config, "workerinput", {}).get("loadgroup"):
        config.option.loadgroup = True


@pytest.hookimpl(optionalhook=True)
def pytest_configure_node(node):
    node.workerinput["loadgroup"] = \
        node.config.getvalue("dist") == "loadgroup"


def dual_cxl_machine():
    """Shared fixture: system-A-like box with one DRAM node and one CXL
    card behind EACH socket — used to exercise origin-dependent tier
    ordering and disjoint-path move overlap."""
    import dataclasses

    from repro.core import MemoryTier
    from repro.topology import TopologyGraph

    g = TopologyGraph("dual-cxl", origin="socket0")
    g.add_node("socket0")
    g.add_node("socket1")
    g.add_node("numa0", kind="numa", tier="DRAM0")
    g.add_node("numa1", kind="numa", tier="DRAM1")
    g.add_node("cxl0", kind="cxl", tier="CXL0")
    g.add_node("cxl1", kind="cxl", tier="CXL1")
    g.add_link("socket0", "numa0", 0.0, 460.8, kind="local")
    g.add_link("socket1", "numa1", 0.0, 460.8, kind="local")
    g.add_link("socket0", "socket1", 87.0, 230.0, kind="upi")
    g.add_link("socket0", "cxl0", 153.0, 38.4, kind="cxl")
    g.add_link("socket1", "cxl1", 153.0, 38.4, kind="cxl")
    dram = MemoryTier("DRAM0", 118, 460.8, 22.0, 256, kind="dram")
    cxl = MemoryTier("CXL0", 118, 38.4, 9.0, 128, kind="cxl")
    tiers = {
        "DRAM0": dram,
        "DRAM1": dataclasses.replace(dram, name="DRAM1"),
        "CXL0": cxl,
        "CXL1": dataclasses.replace(cxl, name="CXL1"),
    }
    return g, tiers
