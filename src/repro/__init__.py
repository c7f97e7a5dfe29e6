"""repro — a JAX PageRank and personalized-PageRank engine built around the
Information Transmitting Algorithm (ITA) for parallel PageRank
(Zhang, Yao, Liang, Zhang 2021), with a serving tier for top-k PPR queries.
"""
__version__ = "1.0.0"
