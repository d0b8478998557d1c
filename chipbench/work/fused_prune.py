"""Bytes the fused pruned route (``planner/device.pruned_batch_device``
-> ``kernels/postings_merge.fused_hit_words``) must read from HBM for one
threshold batch, from the index's shapes and the planner's probe counts.

The least a postings-pruned batch of Gq queries over m records can read:
the compressed body and the 12-byte header (first record, kind and
width, payload offset) of every tail posting block the batch's hashes
touch, a body counted at the store's mean size; each record's buffer
bitmap words, its threshold, size and length (4 B each), since the
buffer part of the estimate needs every record; and the query blob (each
query's packed row, bitmap, threshold, size and length). Buffer posting
blocks are not counted: the bitmaps give the same intersections. The
count does not depend on what implements the route.
"""


def bytes_read(*, tail_blocks: float, store_blocks: int,
               payload_words: int, records: int, buf_words: int,
               queries: float, capacity: int) -> float:
    body = 4 * payload_words / max(store_blocks, 1)
    postings = tail_blocks * (body + 12)
    index = 4 * records * buf_words + 12 * records
    blob = queries * (4 * capacity + 4 * buf_words + 12)
    return float(postings + index + blob)


def shapes(sharded, queries: float, tail_blocks: float) -> dict:
    """The counts of a placed index (a ``ShardedIndex`` whose planner has
    built its postings) for a batch of ``queries`` touching
    ``tail_blocks`` tail blocks."""
    d = sharded.didx
    tail = sharded.host.sketches.postings().tail
    return {"tail_blocks": float(tail_blocks),
            "store_blocks": int(tail.num_blocks),
            "payload_words": int(len(tail.payload)),
            "records": int(d.num_records),
            "buf_words": int(d.buf.shape[1]),
            "queries": float(queries),
            "capacity": int(d.values.shape[1])}
