"""The benchmark: harness, data files, plain references and trace reducer.

Everything that decides a number lives here, where a PR that claims a gain
cannot move it. See README.md.
"""
