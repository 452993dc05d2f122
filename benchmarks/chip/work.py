"""The least HBM traffic one p(l)-CG solution update needs, worked out
from the algorithm and not from any implementation of it.

One steady-state iteration i (i > l) of unpreconditioned p(l)-CG
(Cornelis, Cools, Vanroose, arXiv:1801.04728, Alg. 1, as arXiv:1905.06850
summarises it) touches these length-n vectors, z^(k)_j being basis k's
vector of index j and v_j = z^(0)_j:

* stencil:      A z^(l)_i                         reads z^(l)_i
* top basis:    z^(l)_{i+1} from z^(l)_i, z^(l)_{i-1}  reads z^(l)_{i-1},
                                                  writes z^(l)_{i+1}
* basis k<l:    z^(k)_{i-l+k+1} from z^(k+1)_{i-l+k+1}, z^(k)_{i-l+k},
                z^(k)_{i-l+k-1}                   reads the two z^(k),
                                                  writes one z^(k);
                z^(k+1)_{i-l+k+1} is basis k+1's own older read (or,
                for k = l-1, the stencil's input)
* dot block:    z^(l)_{i+1} against v_{i-2l+1..i-l+1} and
                z^(l)_{i-l+2..i+1}                reads v_{i-2l+1..i-l-2}
                                                  and z^(l)_{i-l+2..i-2};
                the rest are read or written above
* p, x:         p_{i-l} from v_{i-l}, p_{i-l-1};
                x_{i-l+1} = x_{i-l} + zeta p_{i-l} reads p, x; writes p, x

Counting each distinct vector once, however many times the iteration
uses it, gives 2 + 2l + max(0, l-2) + max(0, l-3) + 2 reads and l + 3
writes.  Rows of the ring buffers that the iteration leaves untouched
are not counted, nor are the O(l^2) scalars.  Any implementation moves
at least this much, so time per update times the HBM peak, set against
it, can never exceed 100%.
"""

from __future__ import annotations


def plcg_vectors_read(l: int) -> int:
    if l < 1:
        raise ValueError(f"pipeline depth l must be >= 1, got {l}")
    return 2 + 2 * l + max(0, l - 2) + max(0, l - 3) + 2


def plcg_vectors_written(l: int) -> int:
    if l < 1:
        raise ValueError(f"pipeline depth l must be >= 1, got {l}")
    return l + 3


def plcg_update_min_bytes(n: int, l: int, itemsize: int) -> int:
    """Fewest HBM bytes one p(l)-CG solution update of an n-vector
    system in ``itemsize``-byte words reads and writes."""
    return n * itemsize * (plcg_vectors_read(l) + plcg_vectors_written(l))
