"""Repo tooling (`tools.tfslint`, report renderers, smoke scripts).

A real package (not just loose scripts) so `python -m tools.tfslint`
works from a bare checkout; the standalone scripts (`profile_report.py`,
`endpoint_smoke.py`, `postmortem.py`) keep running as plain files.
"""
