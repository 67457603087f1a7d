"""Spin-flip attempts: the work a sampling cell completes.

One sweep proposes a flip at every site of every replica, so a window of
``sweeps`` sweeps over ``chains`` chains of ``replicas`` lattices of side
``length`` makes ``sweeps * chains * replicas * length**2`` attempts,
whichever chips the replicas are spread over.
"""
from __future__ import annotations


def flip_attempts(sweeps: int, replicas: int, length: int, chains: int = 1) -> int:
    return int(sweeps) * int(chains) * int(replicas) * int(length) ** 2
