"""The least bytes a decode of one row group has to move: what the
roofline share of the decode divides by the chip's bandwidth.

For each decoded column chunk: its compressed bytes (read once), its
non-null values written once at their Parquet physical width, and, for
a byte array, its payload written once.  The sizes come from the
footer (pyarrow's metadata reader: no page is decoded) and the schema;
the byte-array payload, which the footer of these files does not
record, from the generator's arrays.  Nothing here asks the program
what it stages or allocates, so the count is the same whatever
implements the decode.  Offsets, levels and masks are left out: the
count is a floor, and the share it gives can only read low.
"""

from __future__ import annotations

import numpy as np

# bytes per value of each fixed-width physical type
WIDTH = {"BOOLEAN": 1 / 8, "INT32": 4, "INT64": 8, "INT96": 12,
         "FLOAT": 4, "DOUBLE": 8}


def least_bytes(path: str, columns: list, generated: dict) -> list:
    """Least bytes of each row group of ``path`` over ``columns``;
    ``generated`` maps a column to its :class:`~benchmark.datagen.Column`
    (for byte-array payloads)."""
    import pyarrow.parquet as pq

    md = pq.ParquetFile(path).metadata
    names = [md.schema.column(j).path for j in range(md.num_columns)]
    out = []
    lo = 0
    for r in range(md.num_row_groups):
        rg = md.row_group(r)
        hi = lo + rg.num_rows
        total = 0
        for name in columns:
            cc = rg.column(names.index(name))
            nulls = cc.statistics.null_count if cc.is_stats_set else 0
            total += cc.total_compressed_size
            if cc.physical_type == "BYTE_ARRAY":
                c = generated[name]
                total += int(c.offsets[hi] - c.offsets[lo])
            elif cc.physical_type == "FIXED_LEN_BYTE_ARRAY":
                width = md.schema.column(names.index(name)).length
                total += (cc.num_values - nulls) * width
            else:
                total += int(np.ceil((cc.num_values - nulls)
                                     * WIDTH[cc.physical_type]))
        out.append(total)
        lo = hi
    return out
