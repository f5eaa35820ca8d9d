from repro.pgm.datasets import (LDPCInstance, StereoInstance,
                                StereoPairInstance, WORKLOADS,
                                chain_graph, get_workload, ising_grid,
                                ising_grid_fast, ldpc_code, ldpc_graph,
                                list_workloads, loop_graph,
                                protein_like_graph, register_workload,
                                small_ising, stereo_graph, stereo_mrf,
                                stereo_pair_mrf, zoo_stream)

__all__ = ["LDPCInstance", "StereoInstance", "StereoPairInstance",
           "WORKLOADS", "chain_graph",
           "get_workload", "ising_grid", "ising_grid_fast", "ldpc_code",
           "ldpc_graph", "list_workloads", "loop_graph",
           "protein_like_graph", "register_workload", "small_ising",
           "stereo_graph", "stereo_mrf", "stereo_pair_mrf",
           "zoo_stream"]
