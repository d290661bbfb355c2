"""Topology ``erdos_renyi``: the reference's random topology
(``p2pnetwork.cc:62-84``, each pair linked with ``connection_prob``),
drawn by the repo's native C++ builder with ``graph_seed``."""


def csr(config: dict):
    from p2p_gossip_tpu.runtime import native

    return native.native_erdos_renyi(
        int(config["nodes"]), float(config["connection_prob"]),
        int(config["graph_seed"]))
