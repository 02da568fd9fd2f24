"""The least histogram work of one best-first (leaf-wise) tree, as a number of
level passes, so that ``gbdt_iteration.iteration`` counts it unedited.

``gbdt_iteration.iteration(depth=d)`` counts one pass over all ``N`` rows and
``d - 1`` passes over ``N / 2``: the root, then the smaller children of every
level of a depth-wise tree.  A best-first tree of ``L`` leaves has no levels,
but with histogram subtraction it needs just the same two things: the root's
pass over ``N`` rows, and for each of its ``L - 1`` splits a pass over the
smaller child alone (the larger is parent minus smaller).

How many rows are those?  Let ``S(N, L)`` be the most that ``sum min(n_left,
n_right)`` over the splits can be for ``N`` rows and ``L`` leaves; claim ``S <=
(N / 2) log2 L``.  A root split sends ``x N`` rows (``x <= 1/2``) and ``L1``
leaves one way, the rest and ``L2 = L - L1`` the other, so by induction ``S <=
x N + (N / 2) (x log2 L1 + (1 - x) log2 L2)``.  With ``q = L1 / L``, ``x log2
L1 + (1 - x) log2 L2 = log2 L + x log2 q + (1 - x) log2 (1 - q) <= log2 L -
H(x)`` (a cross entropy is at least the entropy), and ``H(x) >= 2 x`` on ``[0,
1/2]`` (``H`` is concave, ``H(0) = 0``, ``H(1/2) = 1``), which gives the claim:
the bound of merging small into large, met by the balanced tree.
So the least work is at most ``N + (N / 2) ceil(log2 L)``: the root and
``ceil(log2 L)`` half passes, ``1 + ceil(log2 L)`` level passes in
``iteration``'s terms (255 leaves: 9, ``5 N`` rows).  A real tree is lopsided
and needs less: ``rows_needed`` says how much, from its covers, and the runner
prints it beside this ceiling, as ``gbdt_iteration`` takes ``N / 2`` a level
for the smaller children of a depth-wise tree.  The count is of what the
algorithm needs whatever implements it: an implementation that expands more
nodes than the selection keeps gets no credit for them.
"""

from __future__ import annotations


def level_passes(num_leaves: int) -> int:
    """``depth`` for ``gbdt_iteration.iteration``: the root's pass and
    ``ceil(log2(num_leaves))`` passes over half the rows."""
    return 1 + max(int(num_leaves) - 1, 0).bit_length()


def rows_needed(left, right, cover) -> float:
    """Rows one tree's histograms really had to read: the root's rows and the
    smaller child of every split.  ``left``/``right`` hold the children of
    each node (a node with ``left[n] <= 0`` is a leaf: node 0 is nobody's
    child), ``cover`` the training rows that reach each node."""
    total = float(cover[0])
    for n in range(len(left)):
        if left[n] > 0:
            total += min(float(cover[left[n]]), float(cover[right[n]]))
    return total
