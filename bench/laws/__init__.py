"""The laws a traffic mix names, one module each, found by name:
`length/<dist>.py` for prompt and output lengths, `arrival/<kind>.py` for
due times, `popularity/<law>.py` for how requests spread over adapters.
A new law is a new file here; `bench/traffic.py` never changes for it.

Every law is deterministic in the count it is asked for: it returns the
distribution's quantiles (lengths, popularity) or a fixed multiset of gaps
that only the seed's generator reorders (arrivals), so every seed serves
the same work in another order."""
