"""Input layer: what the feeder thread spends to produce one queue item
of the window, the FeatureSet's host gather
(``zoo_feed_host_batch_seconds``) and the stack and ``device_put``
(``zoo_feed_shard_seconds``).  It is the pace the input layer can feed
at, whatever the loop waited: batch over it bounds the cell's rate."""

from benchmark.manifest import sibling

grown = sibling(__file__, "_chip").grown


def read(run):
    gather = grown(run, "zoo_feed_host_batch_seconds")
    shard = grown(run, "zoo_feed_shard_seconds")
    # an item is a shard: the gather is probed once more an epoch, when
    # the stream is exhausted
    if gather is None or shard is None or not shard[1]:
        return None
    return (gather[0] + shard[0]) / shard[1] * 1e3
