"""One driver per kind of traffic mix (``"kind"`` in ``mixes/<mix>.json``):
``jobs`` runs clustering jobs back to back, ``open_loop`` serves requests
that arrive on a schedule."""
