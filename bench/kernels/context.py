"""Least work of one VQ context term, out[i] = sum_d val[i, d] *
concat_b cw[b, assign[b, ids[i, d]]] over ``rows`` output rows of
``width`` neighbour slots, ``branches`` product-VQ branches of ``f_blk``
columns, an assignment table over ``nodes`` nodes and ``k`` codewords per
branch.

Operations: one multiply-add per slot and output column.  Bytes: the ids
and values of every slot, the whole assignment table, the codeword tables
and the output, each once (int32 ids and codeword ids, float32 values)."""


def count(rows: int, width: int, branches: int, f_blk: int, nodes: int,
          k: int) -> tuple[float, float]:
    f = branches * f_blk
    ops = 2.0 * rows * width * f
    nbytes = rows * width * 8 + branches * nodes * 4 \
        + branches * k * f_blk * 4 + rows * f * 4
    return ops, float(nbytes)
