"""Work of push PageRank over ``E`` edges and ``n`` vertices, whatever
implements it.  Per iteration each edge is read (4 bytes of source, 4 of
destination) and contributes one multiply and one add; each vertex's rank
is read and written (4 + 4 bytes)."""


def work(E: int, n: int, iters: int):
    ops = iters * 2 * E
    nbytes = iters * (8 * E + 8 * n)
    return ops, nbytes
