"""Graph substrate: structures, synthetic datasets, batching, samplers."""
from repro.graph.structure import CSR, Graph, build_graph, csr_from_coo
from repro.graph.datasets import DATASETS
from repro.graph.batching import (FullGraphOperands, full_operands,
                                  inductive_view, make_pack,
                                  minibatch_stream,
                                  subgraph_operands)
