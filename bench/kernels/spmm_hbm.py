"""Least work of one padded-neighbour SpMM, out[i] = sum_d val[i, d] *
src[idx[i, d]] over ``rows`` output rows of ``width`` neighbour slots and
``f`` columns from a ``src_rows``-row source.

Operations: one multiply-add (2 operations) per slot and column.  Bytes:
each operand once (ids and values of every slot, the source table) and the
output once, float32 values and int32 ids.  Rows are not gathered more than
once in this count, whatever an implementation does."""


def count(rows: int, width: int, f: int, src_rows: int,
          itemsize: int = 4) -> tuple[float, float]:
    ops = 2.0 * rows * width * f
    nbytes = rows * width * (4 + itemsize) + src_rows * f * itemsize \
        + rows * f * itemsize
    return ops, float(nbytes)
