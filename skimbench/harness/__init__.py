"""The skim benchmark harness: set-up, traffic, reference, trace reduction."""
