"""Query personalized recommendations with business rules applied.

The rules act before the top-k, on the device: the answer is the best
``num`` of the items that are left (unseen, available, in the category,
off the blackList), so a category query comes back in full however small
the category is beside the catalog.
"""

import argparse
import json

from predictionio_tpu.client import EngineClient


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--url", default="http://127.0.0.1:8000")
    parser.add_argument("--user", default="u1")
    parser.add_argument("--num", type=int, default=4)
    args = parser.parse_args()
    client = EngineClient(args.url)
    queries = {
        "plain": {"user": args.user, "num": args.num},
        # the quickstart's items are "even" or "odd"
        "one category": {
            "user": args.user, "num": args.num, "categories": ["odd"],
        },
        "blackList": {
            "user": args.user, "num": args.num, "blackList": ["i1", "i3"],
        },
        # a user the model has never seen: similar to their latest views
        # if the store has any, else the popular items
        "unknown user": {"user": "someone-new", "num": args.num},
    }
    for name, query in queries.items():
        print(name, json.dumps(query))
        print(json.dumps(client.send_query(query), indent=2))


if __name__ == "__main__":
    main()
