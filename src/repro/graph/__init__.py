"""Graph substrate: containers, generators, partitioners."""
from .generators import TABLE3_PRESETS, erdos_renyi, paper_dataset, random_dag, web_graph
from .structure import (
    Degrees,
    Graph,
    LiveEdges,
    apply_edge_delta,
    csr_from_graph,
    graph_from_edges,
    validate_graph,
)

__all__ = [
    "Degrees", "Graph", "LiveEdges", "TABLE3_PRESETS", "apply_edge_delta",
    "csr_from_graph",
    "erdos_renyi", "graph_from_edges", "paper_dataset", "random_dag",
    "validate_graph", "web_graph",
]
