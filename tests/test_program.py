"""Setup-phase semantics: graph construction, groups, handles, addresses."""

import pytest

from repro import core as lp
from repro.core.addressing import Address
from repro.core.resources import DEFAULT_GROUP


class Svc:
    def ping(self):
        return "pong"


class Other:
    pass


def test_add_node_returns_handle():
    p = lp.Program("t")
    h = p.add_node(lp.CourierNode(Svc))
    assert isinstance(h, lp.Handle)


def test_pynode_has_no_handle():
    p = lp.Program("t")
    assert p.add_node(lp.PyNode(Svc)) is None


def test_edges_follow_handles():
    p = lp.Program("t")
    h1 = p.add_node(lp.CourierNode(Svc))
    h2 = p.add_node(lp.CourierNode(Svc))
    consumer = lp.CourierNode(Svc, [h1, {"x": h2}])
    p.add_node(consumer)
    edges = p.edges()
    assert len(edges) == 2
    assert all(c is consumer for c, _ in edges)


def test_groups_assign_nodes():
    p = lp.Program("t")
    with p.group("a"):
        p.add_node(lp.CourierNode(Svc))
        p.add_node(lp.CourierNode(Svc))
    p.add_node(lp.CourierNode(Svc))
    assert len(p.groups["a"].nodes) == 2
    assert len(p.groups[DEFAULT_GROUP].nodes) == 1


def test_groups_cannot_nest():
    p = lp.Program("t")
    with pytest.raises(RuntimeError):
        with p.group("a"):
            with p.group("b"):
                pass


def test_group_requires_same_node_type():
    p = lp.Program("t")
    with pytest.raises(TypeError):
        with p.group("a"):
            p.add_node(lp.CourierNode(Svc))
            p.add_node(lp.PyNode(Svc))


def test_unresolved_address_raises_on_dereference():
    p = lp.Program("t")
    h = p.add_node(lp.CourierNode(Svc))
    with pytest.raises(RuntimeError, match="before launch"):
        h.dereference()


def test_address_resolves_once():
    a = Address("x")
    a.resolve("grpc://1.2.3.4:1")
    with pytest.raises(RuntimeError):
        a.resolve("grpc://1.2.3.4:2")


def test_validate_rejects_foreign_handles():
    p1 = lp.Program("a")
    h = p1.add_node(lp.CourierNode(Svc))
    p2 = lp.Program("b")
    p2.add_node(lp.CourierNode(Svc, h))
    with pytest.raises(ValueError, match="does not"):
        p2.validate()


def test_handle_minted_after_add_node_belongs_to_its_node():
    """A handle taken from a node of an already built program still wires
    a new consumer to that node."""
    p = lp.Program("t")
    with p.group("svc"):
        p.add_node(lp.CourierNode(Svc))
    node = p.groups["svc"].nodes[0]
    h = node.create_handle()
    consumer = lp.CourierNode(Other, h)
    p.add_node(consumer)
    p.validate()
    assert p.owner_of(h) is node
    assert p.edges() == [(consumer, node)]


def test_dryrun_launcher_reports_topology():
    p = lp.Program("t")
    with p.group("producer"):
        h1 = p.add_node(lp.CourierNode(Svc))
        h2 = p.add_node(lp.CourierNode(Svc))
    with p.group("consumer"):
        p.add_node(lp.CourierNode(Svc, [h1, h2]))
    launcher = lp.DryRunLauncher()
    launcher.launch(p)
    rep = launcher.report()
    assert len(rep.nodes) == 3
    assert len(rep.edges) == 2
    assert set(rep.groups) == {"producer", "consumer"}
    assert sum(rep.executables.values()) == 3
