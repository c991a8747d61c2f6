"""Arithmetic on time intervals and samples: the part of the yardstick
that every reduction shares. Pure Python, no jax: a test can feed it
hand-made numbers."""


def union(intervals):
    """Merge (start, end) pairs that touch or overlap; sorted, disjoint."""
    merged = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return [tuple(m) for m in merged]


def covered(intervals):
    """Length of the union: time in which at least one interval is open."""
    return sum(end - start for start, end in union(intervals))


def gaps(intervals, window=None):
    """The holes of the union, as (start, end), inside `window` (default:
    from the first start to the last end, so there is no hole at either
    edge)."""
    merged = union(intervals)
    if window is None:
        if not merged:
            return []
        window = (merged[0][0], merged[-1][1])
    holes, cursor = [], window[0]
    for start, end in merged:
        if end <= window[0] or start >= window[1]:
            continue
        if start > cursor:
            holes.append((cursor, start))
        cursor = max(cursor, end)
    if cursor < window[1]:
        holes.append((cursor, window[1]))
    return holes


def overlap(a, b):
    """Length of the intersection of two (start, end) pairs."""
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between the
    two nearest ranks, as numpy's default does."""
    if not values:
        raise ValueError("percentile of no values")
    ordered = sorted(values)
    rank = (len(ordered) - 1) * q / 100.0
    low = int(rank)
    high = min(low + 1, len(ordered) - 1)
    return ordered[low] + (ordered[high] - ordered[low]) * (rank - low)

