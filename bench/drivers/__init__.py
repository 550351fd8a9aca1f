"""General generators, one per kind of traffic mix."""
