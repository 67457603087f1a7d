"""Spin-flip attempts: the work a sampling cell completes.

One sweep proposes a flip at every site of every replica, so a window of
``sweeps`` sweeps over ``chains`` chains of ``replicas`` lattices of shape
``shape`` (of any rank) makes ``sweeps * chains * replicas * prod(shape)``
attempts, whichever chips the replicas are spread over.
"""
from __future__ import annotations

import math


def flip_attempts(sweeps: int, replicas: int, shape, chains: int = 1) -> int:
    return int(sweeps) * int(chains) * int(replicas) * math.prod(int(n) for n in shape)
