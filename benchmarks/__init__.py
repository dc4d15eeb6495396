"""The benchmark: runner, traffic, reductions, references and peaks.

Everything the yardstick needs lives here so that a PR which changes the
program cannot change how it is measured. See README.md.
"""
