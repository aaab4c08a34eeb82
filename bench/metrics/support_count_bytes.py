"""Bytes the support-count kernel has to move, from logical shapes and counters.

Per superstep each chip's miner counts the supports of its popped nodes
against every item of its replica of the database, so it reads the database
once, M items x W words x 4 bytes, and per node reads the node's occurrence
row (W words) and writes one support per item (M int32):

    bytes = supersteps x chips x M x W x 4  +  nodes x (W + M) x 4

M and W are the unpadded item count and ceil(transactions / 32); the
counters are the program's own (`PhaseReport.supersteps`, `n_nodes`).  This
is the read-database-once-per-sweep formula of
`benchmarks/kernel_roofline.py` ((B*W + W*M)*4 read + B*M*4 written) with B
the nodes actually expanded and no padding, so padding, blocking and tile
fusion leave the count as it is.  That file also divides an integer-op count
by an assumed VPU peak (~4.8e12 int-op/s) derived by hand; no published
table gives that peak, so the benchmark's roofline is the HBM bound alone.
"""


def logical_bytes(supersteps: int, nodes: int, *, n_items: int, n_transactions: int,
                  chips: int) -> int:
    words = -(-n_transactions // 32)
    return 4 * (supersteps * chips * n_items * words + nodes * (words + n_items))
