"""Popularity laws: `weights(adapters)` gives each adapter's share of the
requests, in popularity order, from the mix's `adapters` entry."""
