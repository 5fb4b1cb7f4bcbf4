"""How the ring is primed: once, to its final shape, by counted
groups of episodes."""


import random


def prime_groups(staging, corpus, minimum, t_max, seed):
    """The ring's priming, as lists of episodes offered together.  The
    first holds the horizon-length episode, so ``ingest`` sizes T_max
    once.  Then one group per append-program shape (the run's padded
    row total, a multiple of ``_RUN_ROUND``), so every append program
    the window can need is compiled in set-up; then the rest, to
    exactly ``minimum`` episodes, in an order drawn from ``seed`` (the
    same episodes in every run, in other slots)."""
    def rows(ep):
        return -(-ep["steps"] // staging._GROW_ROUND) * staging._GROW_ROUND

    longest, pool = corpus[0], corpus[1:]
    by_rows = sorted(pool, key=rows)
    run, bucket = staging._MAX_RUN, staging._RUN_ROUND
    groups = []
    for b in range(1, run * t_max // bucket + 1):
        lo, hi = (b - 1) * bucket, b * bucket
        group = []
        while len(group) < run and sum(map(rows, group)) + rows(longest) <= hi:
            group.append(longest)
        for ep in by_rows:
            total = sum(map(rows, group))
            if total > lo or len(group) == run:
                break
            if total + rows(ep) <= hi:
                group.append(ep)
        if lo < sum(map(rows, group)) <= hi:
            groups.append(group)
    used = sum(len(g) for g in groups)
    if used > minimum:
        raise RuntimeError(
            f"priming every append shape takes {used} episodes, more "
            f"than minimum_episodes {minimum}")
    pool = list(pool)
    random.Random(seed).shuffle(pool)
    rest = (pool * (1 + minimum // max(len(pool), 1)))[:minimum - used]
    return groups, rest
