"""Arrival laws: `count(spec, seconds)` requests fall due in a window of
`seconds`, and `due(spec, n, rng)` gives their due times in seconds from
the window's start. `rng` only reorders a multiset the law fixes."""
