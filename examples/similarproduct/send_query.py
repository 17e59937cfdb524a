"""Query items similar to the given items, with optional filters.

Each of the engine's algorithms (``view``, ``like``) scores an item by
the summed cosine to every item of ``--items`` that it knows, keeps the
candidates the filters leave (none of ``--items``, off ``--blacklist``,
on ``--whitelist`` if given, in one of ``--categories`` if given, score
above 0) and takes the best ``--num`` of those: the rules act before the
top-k, so a small category still answers in full. The Serving then
standardizes each algorithm's list (z-scores by its own mean and sample
deviation, unless ``--num`` is 1), sums the z of an item over the lists
that hold it and answers the ``--num`` largest sums: the scores printed
are those sums, not cosines."""

import argparse
import json

from predictionio_tpu.client import EngineClient


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--url", default="http://127.0.0.1:8000")
    parser.add_argument("--items", default="i0", help="comma-separated")
    parser.add_argument("--num", type=int, default=4)
    parser.add_argument("--categories", default=None)
    parser.add_argument("--whitelist", default=None, help="comma-separated")
    parser.add_argument("--blacklist", default=None, help="comma-separated")
    args = parser.parse_args()
    query = {"items": args.items.split(","), "num": args.num}
    if args.categories:
        query["categories"] = args.categories.split(",")
    if args.whitelist:
        query["whiteList"] = args.whitelist.split(",")
    if args.blacklist:
        query["blackList"] = args.blacklist.split(",")
    print(json.dumps(EngineClient(args.url).send_query(query), indent=2))


if __name__ == "__main__":
    main()
